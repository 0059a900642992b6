"""The program's tracing layer: host spans, device scopes, counts of
compiles and of the attention core's paths.

Spans are ``jax.profiler.TraceAnnotation``s named ``repro.<name>``: they
sit on the profiler's clock beside the device trace, and exist only
while a profiler session records. With none recording, :func:`span`
returns one shared null context after a single check, and an argument
given as a callable is never called. Scopes are ``jax.named_scope``s:
they reach each device op as the ``op_name`` of its HLO metadata.

The names below are shared by the program and by the readers of its
traces, so the two cannot drift apart.
"""

from __future__ import annotations

import collections
from typing import Dict, Tuple

import jax

# device scopes
FORWARD = "zo.forward"           # one perturbed loss evaluation
UPDATE = "zo.update"             # the update rule's sweep over params
LM_HEAD = "runtime.lm_head"      # projection to vocabulary logits
LOSS = "runtime.loss"            # cross entropy over the logits
ATTENTION = "runtime.attention"  # attention core: scores, mask, softmax, sum
CLS_HEAD = "runtime.cls_head"    # classifier: pooling, tanh, 2-class head
MATMUL = "zo_matmul."            # + a projection's parameter path

# attention-core paths, counted as traced
ATTN_KERNEL = "flash_attention"  # the Pallas kernel
ATTN_JNP = "attention"           # the jnp reference path

# host spans
PREFIX = "repro."
COMPILE = "jit.compile"          # one backend compile (or cache load)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_recording = jax.profiler.TraceAnnotation.is_enabled


class _Null:
    """The span when no trace records: enters, exits, drops metadata."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **_):
        pass


_NULL = _Null()


def span(name: str, **args):
    """Host span ``repro.<name>`` carrying ``args`` while a trace
    records (a callable arg is called then, and only then; a None arg is
    left out); otherwise the shared null context. ``set_metadata(**kw)``
    on the entered span adds args known only inside it."""
    if not _recording():
        return _NULL
    return jax.profiler.TraceAnnotation(
        PREFIX + name, **{k: v() if callable(v) else v
                          for k, v in args.items() if v is not None})


# compiles, by the name of the jitted function
_compiles: Dict[str, list] = collections.defaultdict(lambda: [0, 0.0])


def _on_duration(event: str, secs: float, fun_name: str = "?", **_) -> None:
    if event != _COMPILE_EVENT:
        return
    c = _compiles[fun_name]
    c[0] += 1
    c[1] += secs
    if _recording():
        with jax.profiler.TraceAnnotation(PREFIX + COMPILE, fun=fun_name,
                                          ms=secs * 1e3):
            pass


def compiles() -> Dict[str, Tuple[int, float]]:
    """Backend compiles (or persistent-cache loads) since import, per
    function name: (count, seconds)."""
    return {k: (n, s) for k, (n, s) in _compiles.items()}


jax.monitoring.register_event_duration_secs_listener(_on_duration)


# attention cores, by the path each was traced onto
_attention_cores: Dict[str, int] = collections.Counter()


def attention_core(path: str) -> None:
    """Count one attention core traced onto ``path`` (ATTN_KERNEL or
    ATTN_JNP); a layer scan traces its body, and so counts, once."""
    _attention_cores[path] += 1


def attention_cores() -> Dict[str, int]:
    """Attention cores traced since import, per path."""
    return dict(_attention_cores)
