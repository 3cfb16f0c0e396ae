"""The floor under every Pallas kernel module of this repo: whether a kernel is
interpreted, the scoped VMEM it asks for, and a kernel body traced once a signature.

Callers ask the MODULE (``pallas_common.use_interpret()``), never a name bound by
``from ... import``: lowering the real Mosaic kernels off the chip (a described v5e:
``tests/test_topology_aot.py``, ``experiments/step_text_digest.py``) is then ONE patch,
``pallas_common.use_interpret = lambda: False``, and reaches every kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.extend
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

#: scoped VMEM the kernels ask for. Mosaic's default per-kernel budget is ~16 MB, but
#: the v5e runs kernels with >= 120 MB resident blocks when ``vmem_limit_bytes`` is
#: raised (measured on the chip: BASELINE.md round 5); 64 MB makes the combined blocked
#: backward legal at the 7B shape (s = 4096: 21.4 MB scoped) and the envelopes of
#: `flash_attention._seq_envelope`, `ssd.scan_path` and `gated_delta.scan_path` count in it
VMEM_LIMIT_MB = 64


def use_interpret() -> bool:
    return jax.default_backend() == "cpu"


def compiler_params(**kw):
    kw.setdefault("vmem_limit_bytes", VMEM_LIMIT_MB << 20)
    return pltpu.CompilerParams(**kw)


@functools.lru_cache(maxsize=None)
def _traced(fn, avals, static, interpret):
    del interpret  # a key: the kernels bind it while they are traced
    return jax.make_jaxpr(functools.partial(fn, **dict(static)))(*avals)


def traced_once(fn, *args, **static):
    """``fn(*args, **static)`` (one array out), with ``fn`` traced once a signature
    and its jaxpr evaluated at every other call site. A step program calls each
    kernel of a held share's path 4 layers x (forward, replay, backward) times; a
    `pl.pallas_call` traces its kernel body anew at each, which the set-up of every
    run pays, warm cache or not (PERF.md §6, PR 49). The caller's name stack is
    kept: `eval_jaxpr` puts it in front of the equations' own."""
    avals = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args)
    closed = _traced(fn, avals, tuple(sorted(static.items())), use_interpret())
    return jax.extend.core.jaxpr_as_fun(closed)(*args)[0]


# -- a ring's arc (the decode kernels' block arithmetic) ---------------------------------


def ring_steps(window: int, span: int, places: int, block: int) -> int:
    """Grid steps a row of a ring of ``places`` places takes in key blocks of ``block``
    (static): the most blocks the arc of `ring_arc` touches wherever it starts, an arc
    of ``span + window - 1`` places, and no more than the ring has."""
    return min(places // block, -(-(span + window - 2) // block) + 1)


def ring_arc(first, window: int, span: int, places: int, block: int):
    """Of a ring (position p at place ``p mod places``) in key blocks of ``block``, the
    ARC a window of ``window`` queries at ``first ..`` sees under a span of ``span``:
    positions ``(first - span, first + window - 1]``, none before 0, which lie in the ring
    as one run of places that may wrap its end -> (the block the oldest of them lies in,
    the blocks the run touches, no more than the ring has: a block is masked by the
    positions it holds, so one visit serves both ends of a run that comes round to it).
    Block ``(first block + j) mod (places // block)`` for ``j`` below the count is the walk.
    ``first``: a traced scalar (an index map, a kernel body) or a host integer (the
    engine's counters, once a row an iteration: plain integers, no array)."""
    most, least = (jnp.maximum, jnp.minimum) if isinstance(first, jax.Array) else (max, min)
    oldest = most(first - span + 1, 0)
    at = oldest % places
    touched = (at % block + first + window - oldest - 1) // block + 1
    return at // block, least(touched, places // block)
