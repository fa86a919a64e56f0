"""Span tracing: nestable timed stages, named device layers, JAX profiler hooks.

``span("ingest")`` times a runtime stage with ``time.perf_counter`` and
emits a ``jax.profiler.TraceAnnotation`` for its dynamic extent, so the
same stage names land in perfetto/TensorBoard traces captured with
:func:`profile`. Spans nest per-thread: a span opened inside another
records under the joined path (``"ingest/publish"``), which is also the
``stage`` label of the ``span_seconds`` histogram when a registry is
passed. A span given no registry records into its parent's, so code
below a session (the engine) lands in the session's registry without
one being passed down.

    reg = MetricsRegistry()
    with span("ingest", reg):
        with span("scan"):          # recorded as "ingest/scan" into reg
            ...
    reg.get("span_seconds").labels(stage="ingest").percentile(99)

Inside a jitted program, :func:`layer` names a layer of the engine
(one of :data:`LAYERS`) as a ``jax.named_scope``. The compiler keeps the
scope path in each instruction's ``op_name`` metadata; executables
handed to :func:`register_program` are mapped, on the first call of
:func:`op_layers`, from instruction name to the innermost layer, which
is how a device trace's ops (named by instruction) are attributed.

One-call profiler capture (writes a trace viewable in TensorBoard's
profile plugin or perfetto)::

    with obs.profile("/tmp/jax-trace"):
        session.ingest(users, items)
"""

from __future__ import annotations

import collections
import contextlib
import re
import threading
import time

import jax

__all__ = ["span", "profile", "current_span", "LAYERS", "layer",
           "register_program", "op_layers"]

#: The engine's layers inside the scan program, in the order a step
#: runs them. ``column_clear`` nests in ``update``; the innermost wins.
LAYERS = ("route", "score", "update", "column_clear", "telemetry", "forget")

_tls = threading.local()


def current_span() -> str:
    """The calling thread's open span path ("" outside any span)."""
    return "/".join(name for name, _ in getattr(_tls, "stack", ()))


@contextlib.contextmanager
def span(name: str, registry=None):
    """Time a stage; record into ``registry``'s (or the enclosing span's)
    ``span_seconds{stage=...}`` histogram. Yields the full span path."""
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    if registry is None and stack:
        registry = stack[-1][1]
    stack.append((name, registry))
    path = "/".join(n for n, _ in stack)
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(path):
            yield path
    finally:
        dt = time.perf_counter() - t0
        stack.pop()
        if registry is not None:
            registry.histogram(
                "span_seconds", "Wall time of runtime stages",
                labels=("stage",)).labels(stage=path).observe(dt)


def layer(name: str):
    """``jax.named_scope`` for one of the engine's :data:`LAYERS`."""
    if name not in LAYERS:
        raise ValueError(f"unknown layer {name!r}; one of {LAYERS}")
    return jax.named_scope(name)


# Registered executables, each ``[compiled or None, parsed map or None]``;
# parsing drops the executable. Bounded like the engine's compile cache.
_programs: collections.deque = collections.deque(maxlen=16)
_programs_lock = threading.Lock()

_MODULE = re.compile(r"^HloModule ([^\s,]+)", re.M)
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*)$", re.M)
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")


def register_program(compiled) -> None:
    """Remember a compiled executable for :func:`op_layers`. Holds a
    reference only; nothing is read until :func:`op_layers` is called."""
    with _programs_lock:
        _programs.append([compiled, None])


_TRANSFORM = re.compile(r"^(?!jit\()\w+\((.*)\)$")


def _innermost_layer(op_name: str) -> str:
    """The last path component that names a layer; a transform wraps
    its scope's name (``vmap(score)``), a ``jit(f)`` is a function."""
    for part in reversed(op_name.split("/")):
        while (m := _TRANSFORM.match(part)) is not None:
            part = m.group(1)
        if part in LAYERS:
            return part
    return ""


def _parse(text: str) -> dict:
    """``{module: {instruction: layer or ""}}`` of one ``as_text()``.

    An instruction the compiler added without metadata (a layout copy,
    a cloned iota) takes the layer of its users when they all have the
    same one."""
    out: dict = {}
    heads = list(_MODULE.finditer(text))
    for j, head in enumerate(heads):
        end = heads[j + 1].start() if j + 1 < len(heads) else len(text)
        where: dict = {}
        users = collections.defaultdict(set)
        for m in _INSTR.finditer(text, head.end(), end):
            name, rest = m.groups()
            meta = _OP_NAME.search(rest)
            where[name] = _innermost_layer(meta.group(1)) if meta else None
            for operand in _OPERAND.findall(rest):
                users[operand].add(name)
        open_ = [n for n, w in where.items() if w is None]
        while open_:
            still = []
            for n in open_:
                got = {where.get(u) for u in users[n]}
                if len(got) == 1 and next(iter(got)):
                    where[n] = got.pop()
                else:
                    still.append(n)
            if len(still) == len(open_):
                break
            open_ = still
        out[head.group(1)] = {n: w or "" for n, w in where.items()}
    return out


def op_layers() -> dict:
    """``{module name: {instruction name: innermost layer or ""}}`` over
    the registered programs. Parsed on the first call after a program
    is registered (never on the dispatch path). An instruction that two
    programs of one module name place in different layers is unscoped."""
    with _programs_lock:
        for entry in _programs:
            if entry[1] is None:
                entry[1] = _parse(entry[0].as_text())
                entry[0] = None
        parsed_all = [parsed for _, parsed in _programs]
    merged: dict = {}
    for parsed in parsed_all:
        for module, ops in parsed.items():
            have = merged.setdefault(module, {})
            for name, where in ops.items():
                have[name] = where if have.get(name, where) == where else ""
    return merged


@contextlib.contextmanager
def profile(log_dir: str):
    """Capture a JAX profiler trace of the block into ``log_dir``."""
    with jax.profiler.trace(str(log_dir)):
        yield log_dir
