"""The search's price of the communication on the critical path over what the
core spent on it: ``tp_exposed + dp_exposed + cp + ep + redistribute +
other_comm + pp_p2p`` of the plan's ``time_ms`` (``search/price.py``) over the
quantity ``comm_scope_ms_per_step`` reads (collectives, operations under
``grad_sync`` / ``redistribute`` / ``allgather_einsum`` /
``einsum_reducescatter``, collective ``fusion:kCustom``).  1.0 is the aim;
recorded, not gated.  Printed beside it: the time a collective is in flight and
its exposed part, the predicted ``volume_mb`` by term, and the bandwidth each
implies over the time in flight against the GB/s the price assumed."""

from benchmark.lib import xplane
from benchmark.metrics import _search_terms

NAME, UNIT, BETTER, SOURCE = "search_comm_pred_over_meas", "ratio", "lower", "program_counter"
LAYER, MOVES = "search", "tokens_per_s_per_chip"


def compute(ctx):
    t = _search_terms.of_ctx(ctx)
    if t is None or t["comm_ratio"] is None:
        return None
    price, say = t["price"], ctx["say"]
    flight, exposed = xplane.collective_ns(xplane.first_device(ctx["trace"]))
    flight_ms, n = flight / 1e6 / ctx["n_profiled"], ctx["n_profiled"]
    say(f"  comm in flight {flight_ms:.3f} ms a step, exposed in flight {exposed / 1e6 / n:.3f}")
    assumed = price.get("basis", {}).get("assumed_gbps", {})
    volume = price.get("volume_mb", {})
    for term, mb in sorted(volume.items(), key=lambda kv: -kv[1]):
        implied = f"{mb / flight_ms:.1f}" if flight_ms else "n/a"
        say(f"  volume {term}: {mb:.1f} MB a step predicted; over the time in flight it implies "
            f"{implied} GB/s, the price assumed {assumed.get(term, float('nan')):.1f}")
    if volume and flight_ms:
        say(f"  volume, all terms: {sum(volume.values()):.1f} MB over {flight_ms:.3f} ms in flight = "
            f"{sum(volume.values()) / flight_ms:.1f} GB/s")
    return t["comm_ratio"]
