"""What the readers of a latent-attention (MLA) serving cell share (PR 51).  The
program's cached forward of such a stack opens, under ``layer_<i>`` > ``attn``,
the scopes the serving readers know (``qkv_proj``, ``cache_write``, ``attn_core``,
``out_proj``) and two of its own below ``attn_core``: ``absorb`` (a decode step's
two absorbed products) and ``expand`` (a prompt chunk's ``W_kvb`` expansion); its
expert layers open ``router``, ``dispatch``, ``experts``, ``combine`` and
``shared_expert`` under ``mlp``.  Its ``decode`` spans carry the iteration's
counters: ``latent_cache_bytes_per_position`` (all layers), ``latent_live_positions``,
``moe_held_pairs_per_token``, ``moe_load_imbalance``.

A serving reader names no cell (``tests/benchmark/test_benchmark_manifest.py``):
it is read wherever ``serve_tokens_per_s_per_chip`` is.  So in a window whose
programs ran but carry none of those scopes or counters (a stack of plain
attention with dense MLPs: ``opt-1.3b``; a program from before this PR) every
reader answers 0, which is what such a step spends under a latent attention or a
routed expert.  None, and the metric left out, in a context without ``serve`` or
with nothing to read at all (no trace for a device reader, no ``decode`` span for
a counter)."""

import re

from benchmark.lib import scoped
from benchmark.lib.stats import percentile

_WRAPPED = re.compile(r"^(?:[A-Za-z_]\w*\()+([^()]*)\)+$")


def path_of(op_name):
    """Every part of an ``op_name`` path with autodiff's wrappers taken off."""
    out = []
    for part in op_name.rstrip(":").split("/"):
        m = _WRAPPED.match(part)
        out.append(m.group(1) if m else part)
    return out


def program_runs(ctx, part):
    """Device 0's executions of the window's program whose jit name holds ``part``
    (``decode`` | ``prefill``), the one with most device time; [] where there is none."""
    if "serve" not in ctx:
        return []
    progs = {k: v for k, v in scoped.by_program(scoped.executions(ctx) or []).items() if part in k}
    if not progs:
        return []
    return max(progs.values(), key=lambda runs: sum(map(scoped.busy_ns_of, runs)))


def scope_ms_p50(ctx, part, wanted, mark):
    """Median over the executions of program ``part`` of the device time of the
    operations whose path holds a scope of ``wanted``; 0 unless some operation of
    the program carries a scope of ``mark`` (what tells an MLA or an expert layer's
    program from another); None where no such program ran under the trace."""
    wanted, mark = set(wanted), set(mark)
    per_run, marked = [], False
    for ex in program_runs(ctx, part):
        paths = [(o, set(path_of(o.op_name))) for o in ex.ops]
        marked = marked or any(path & mark for _, path in paths)
        per_run.append(sum((o.end - o.start) / 1e6 for o, path in paths if path & wanted))
    if not per_run:
        return None
    return percentile(per_run, 50) if marked else 0.0


def decode_counter(ctx, key):
    """The window's ``decode`` spans' values of the counter ``key``: [] where the
    iterations carry none, None where there is no iteration."""
    spans = [s for s in ctx["spans"] if s["name"] == "decode"] if "serve" in ctx else []
    if not spans:
        return None
    return [float(s["args"][key]) for s in spans if isinstance(s["args"].get(key), (int, float))]


def latent_step_bytes(live_positions, new_positions, bytes_per_position):
    """Least HBM bytes of ONE decode step's cached latent attention, all layers:
    the live positions' latent read once and the new positions' written
    (``bytes_per_position`` = layers x (kv_lora_rank + qk_rope_head_dim) x itemsize;
    the weights are ``qkv_proj``'s and ``absorb`` reads W_kvb, 16.8 MB a layer: left
    out, so the share reads low, never high)."""
    return (live_positions + new_positions) * bytes_per_position
