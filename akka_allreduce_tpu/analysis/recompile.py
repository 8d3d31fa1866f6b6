"""Recompilation guard: "a warmed step never recompiles", asserted.

The serving engine's throughput story (serving/engine.py: slot churn
and refill never change the program) and the train loop's compile-cache
stability (models/train.py: one program per shape) are claims about
what the JAX dispatch layer does at *runtime* — invisible to the jaxpr
passes. This module counts compiles instead: JAX's ``jax_log_compiles``
flag logs one "Compiling <name> ..." record per trace-cache miss
(jax._src.interpreters.pxla), emitted whether or not the persistent
compilation cache then serves the executable — which is exactly the
recompile definition that matters (a new program was built; dispatch
stalled on it). The guard installs a logging handler on that logger,
tallies the records, and restores everything on exit.

Usage::

    with no_recompiles():              # warmed hot loop: 0 new programs
        engine.step()

    with assert_max_compiles(3) as log:  # bounded warmup
        run()
    assert log.count == 3, log.compiled  # which programs, for the diff

Process-wide (JAX's compile path is), not thread-safe; nesting works —
each guard counts compiles inside its own window.
"""

from __future__ import annotations

import logging
import re
from typing import Optional

import jax

# the pxla module that owns the "Compiling jit(<name>) with global shapes
# and types ..." record (jax 0.9.0; pinned by tests/test_analysis)
_COMPILE_LOGGERS = ("jax._src.interpreters.pxla",)
# loggers that get chatty at WARNING while jax_log_compiles is on; the
# guard silences their propagation for its window so enabling the flag
# does not spray compile timings over the program's stderr
_QUIET_LOGGERS = ("jax._src.interpreters.pxla", "jax._src.dispatch",
                  "jax._src.compiler")
# The guard's job is COUNTING: any record whose message starts with the
# "Compiling " prefix IS a compile, counted unconditionally — as
# "<unparsed>" if the jit(<name>) form ever stops matching, so a format
# drift can blind the name-keyed contracts (loudly: "<unparsed>" matches
# no hot name and shows in every diff) but never zero the count.
_COMPILE_PREFIX = "Compiling "
_COMPILE_RE = re.compile(r"^Compiling jit\((.+?)\) with ")


def _compiled_name(message: str) -> Optional[str]:
    """The jitted function's name from a pxla compile record, or None
    when the record is not a compile record at all. NEVER returns None
    for a "Compiling ..."-prefixed message: an unparsable name degrades
    to "<unparsed>", not to an uncounted compile."""
    if not message.startswith(_COMPILE_PREFIX):
        return None
    m = _COMPILE_RE.match(message)
    return m.group(1) if m else "<unparsed>"


class RecompileError(AssertionError):
    """A guarded region compiled more programs than its contract allows."""


class _CountingHandler(logging.Handler):
    def __init__(self, sink: "CompileLog"):
        super().__init__(level=logging.DEBUG)
        self._sink = sink

    def emit(self, record: logging.LogRecord) -> None:
        name = _compiled_name(record.getMessage())
        if name is not None:
            self._sink.compiled.append(name)


class CompileLog:
    """Context manager that records every program compiled inside its
    window. ``compiled`` is the list of program names (jit-decorated
    function names, in compile order); ``count`` its length."""

    def __init__(self) -> None:
        self.compiled: "list[str]" = []
        self._handler: Optional[_CountingHandler] = None
        self._prev_flag: Optional[bool] = None
        self._prev_levels: "list[tuple[logging.Logger, int]]" = []
        self._prev_propagate: "list[tuple[logging.Logger, bool]]" = []

    @property
    def count(self) -> int:
        return len(self.compiled)

    def __enter__(self) -> "CompileLog":
        self._prev_flag = jax.config.jax_log_compiles
        jax.config.update("jax_log_compiles", True)
        self._handler = _CountingHandler(self)
        for name in _COMPILE_LOGGERS:
            logger = logging.getLogger(name)
            # the record is emitted at WARNING when the flag is on; the
            # logger must not filter it out (NOTSET inherits root, which
            # passes WARNING — but a suite that quieted jax.* to ERROR
            # would silently blind the guard)
            self._prev_levels.append((logger, logger.level))
            if logger.getEffectiveLevel() > logging.WARNING:
                logger.setLevel(logging.WARNING)
            logger.addHandler(self._handler)
        self._null = logging.NullHandler()
        for name in _QUIET_LOGGERS:
            logger = logging.getLogger(name)
            self._prev_propagate.append((logger, logger.propagate))
            # propagate=False keeps the records away from root handlers;
            # the NullHandler keeps logging's lastResort (which prints
            # WARNING+ to stderr when NO handler is found) out of play
            logger.propagate = False
            logger.addHandler(self._null)
        return self

    def __exit__(self, *exc) -> None:
        for logger, prop in self._prev_propagate:
            logger.removeHandler(self._null)
            logger.propagate = prop
        self._prev_propagate.clear()
        for name in _COMPILE_LOGGERS:
            logging.getLogger(name).removeHandler(self._handler)
        for logger, level in self._prev_levels:
            logger.setLevel(level)
        self._prev_levels.clear()
        jax.config.update("jax_log_compiles", self._prev_flag)


class assert_max_compiles:
    """Fail (RecompileError) if the window compiles more than
    ``limit`` programs. The error names every program compiled, so the
    diff from "expected 0, got 1: engine_prefill" reads directly."""

    def __init__(self, limit: int, what: str = "guarded region"):
        self.limit = limit
        self.what = what
        self._log = CompileLog()

    @property
    def count(self) -> int:
        return self._log.count

    @property
    def compiled(self) -> "list[str]":
        return self._log.compiled

    def __enter__(self) -> "assert_max_compiles":
        self._log.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self._log.__exit__(exc_type, exc, tb)
        if exc_type is None and self._log.count > self.limit:
            raise RecompileError(
                f"{self.what}: {self._log.count} program(s) compiled, "
                f"contract allows {self.limit}: "
                f"{', '.join(self._log.compiled)} — a warmed step "
                f"function recompiled (shape/dtype/static-arg drift, "
                f"or a weak-type scalar reached the jit boundary)")


def no_recompiles(what: str = "warmed step") -> assert_max_compiles:
    """The post-warmup contract: zero compiles in the window."""
    return assert_max_compiles(0, what=what)

