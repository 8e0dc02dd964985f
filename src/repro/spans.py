"""The program's own names in a profiler trace.

Two kinds, both read with ``jax.profiler`` (XProf, or ``ProfileData`` over
the ``.xplane.pb`` a capture writes):

* **host spans** — :func:`span` opens a ``jax.profiler.TraceAnnotation``
  named ``"suco." + name`` on the thread that runs it.  They land on the
  profiler's host clock, the same clock as every other annotation of the
  process, so a gap in which the chip sat idle can be put down to the
  span the host was in.  Without an active profiler session one costs
  about a microsecond.
* **device stages** — :func:`scope` is ``jax.named_scope``: it names the
  ops traced inside it in the HLO metadata (the op's name stack), so each
  op the chip runs carries the stage it belongs to.  It changes metadata
  only, never the computation.

Every name used is one of the constants below.
"""

from __future__ import annotations

import jax

PREFIX = "suco."

#: stages of the fused query executable (``core/suco.py``)
QUERY_STAGES = ("activate", "score", "rerank", "merge", "fallback", "select")
#: stages of the index build (``core/suco.py:_build``, ``core/kmeans.py``)
BUILD_STAGES = ("halves", "kmeans.init", "kmeans.lloyd", "kmeans.assign")
#: host spans of the servers' step (``serve/ann.py``)
SERVE_SPANS = ("serve.form", "serve.dispatch", "serve.wait", "serve.deliver")
#: host spans of ``build_index``
BUILD_SPANS = ("build.plan", "build.dispatch")

_SPANS = frozenset(SERVE_SPANS + BUILD_SPANS)
_SCOPES = frozenset(QUERY_STAGES + BUILD_STAGES)


def span(name: str) -> jax.profiler.TraceAnnotation:
    """Host span ``"suco." + name``; ``name`` is one of the span constants."""
    if name not in _SPANS:
        raise KeyError(f"unknown span {name!r}")
    return jax.profiler.TraceAnnotation(PREFIX + name)


def scope(name: str):
    """Device stage ``name`` (a ``jax.named_scope``); one of the stage
    constants."""
    if name not in _SCOPES:
        raise KeyError(f"unknown stage {name!r}")
    return jax.named_scope(name)
