"""Device 0's time a step in communication, by name: the collectives that name
themselves (``xplane``'s ``collective`` category), the operations under the
scopes that are communication by construction (``grad_sync``, ``redistribute``,
``allgather_einsum``, ``einsum_reducescatter``), and every ``fusion:kCustom``
whose ``hlo_category`` in the trace names a collective.  Busy time, not time
in flight: ``collective_ms_per_step`` has that.  The parts are printed."""

from benchmark.lib import scoped

NAME, UNIT, BETTER, SOURCE = "comm_scope_ms_per_step", "ms", "lower", "device_trace"
LAYER, MOVES = "runtime and plan", "tokens_per_s_per_chip"


def compute(ctx):
    sops = scoped.device0(ctx)
    if sops is None:
        return None
    n = ctx["n_profiled"]
    comm = [o for o in sops if scoped.is_comm(o)]
    if not comm:
        return None
    parts = {}
    for o in comm:
        key = (o.category if o.category != "fusion:kCustom" else f"fusion:kCustom[{o.hlo_category}]",
               scoped.second_level(o.op_name))
        parts[key] = parts.get(key, 0.0) + (o.end - o.start)
    for (cat, scope), ns in sorted(parts.items(), key=lambda kv: -kv[1])[:12]:
        ctx["say"](f"  comm {ns / 1e6 / n:.3f} ms a step: {cat} under {scope}")
    return sum(o.end - o.start for o in comm) / 1e6 / n
