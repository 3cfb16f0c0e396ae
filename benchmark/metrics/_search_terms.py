"""What the five ``search_*`` readers share: the search's price of the plan AS
RUN, term by term (``galvatron_tpu.obs.flight.last_plan_price()``: the trainer
keeps what ``search/price.price_plan`` charges the plan it trains, whether the
plan file carried it or the trainer priced a flag plan itself), beside device
0's profiled steps cut into the parts those terms claim to price.

Measured parts, each leaf operation of device 0 in exactly one (mean over the
profiled steps, as every scope reader takes it):

- ``comm``: a collective, or under ``grad_sync`` / ``redistribute`` /
  ``allgather_einsum`` / ``einsum_reducescatter``, or a ``fusion:kCustom`` whose
  ``hlo_category`` names a collective: ``scoped.is_comm``, what
  ``comm_scope_ms_per_step`` reads;
- ``other``: under ``embed`` / ``head`` / ``loss``, forward and backward, less
  its comm part;
- ``compute``: under ``layer_<i>`` or ``grad_accum``, less its comm part;
- ``optimizer``: under ``optimizer``;
- ``unscoped``: everything else (compiler-inserted copies and waits);
- ``idle``: the device's window less its busy time.

Predicted side, the terms of ``time_ms`` (``search/price.py`` names them):
``compute + overlap_slowdown`` against ``compute``; ``tp_exposed + dp_exposed +
cp + ep + redistribute + other_comm + pp_p2p`` against ``comm``;
``other_compute`` against ``other``; nothing prices ``optimizer``, ``unscoped``
or ``idle`` (``pp_bubble`` is printed against ``idle`` and joins no ratio until
a pipeline cell exists).  1.0 is the aim of every ratio; none is gated.

A program without ``last_plan_price`` (a parent before it), a price that is an
``{"error": ...}``, or a run without a device trace gives None: the five
metrics then leave themselves out.
"""

from benchmark.lib import scoped, xplane
from benchmark.lib.stats import percentile

#: time terms priced on the critical path as communication
COMM_TERMS = ("tp_exposed", "dp_exposed", "cp", "ep", "redistribute", "other_comm", "pp_p2p")
COMPUTE_TERMS = ("compute", "overlap_slowdown")
#: terms the model believes hidden, and the bubble: printed, in no ratio
BESIDE_TERMS = ("dp_hidden", "tp_hidden", "pp_bubble", "pipeline_coupled")
MEASURED_PARTS = ("compute", "comm", "other", "optimizer", "unscoped", "idle")


def plan_price():
    """``flight.last_plan_price()``, or None: no such accessor in this program,
    nothing priced, or a price that is an error."""
    try:
        from galvatron_tpu.obs import flight
    except ImportError:
        return None
    fn = getattr(flight, "last_plan_price", None)
    price = fn() if fn else None
    if not price or "error" in price or "time_ms" not in price:
        return None
    return price


def part_of(o) -> str:
    """The measured part one of device 0's leaf operations belongs to."""
    if scoped.is_comm(o):
        return "comm"
    if "optimizer" in scoped.scopes_of(o.op_name):
        return "optimizer"
    first = scoped.model_scopes(o.op_name)[:1]
    if first and first[0] in scoped.HEAD_SCOPES:
        return "other"
    if first and first[0] in ("layer", "grad_accum"):
        return "compute"
    return "unscoped"


def measured_ms(sops, ops, n):
    """``{part: ms a step}`` of device 0: its leaf operations by ``part_of``
    and the idle rest of its window; the parts add up to the window over ``n``."""
    out = {part: 0.0 for part in MEASURED_PARTS}
    for o in sops:
        out[part_of(o)] += (o.end - o.start) / 1e6 / n
    a, b = xplane.window_of(ops)
    out["idle"] = ((b - a) - xplane.busy_ns(ops)) / 1e6 / n
    return out


def predicted_ms(price):
    """``{part: ms}`` of the price's critical-path terms by the measured part
    each claims to price; what no ratio reads is under its own term's name."""
    t = price["time_ms"]
    out = {"compute": sum(t.get(k, 0.0) for k in COMPUTE_TERMS),
           "comm": sum(t.get(k, 0.0) for k in COMM_TERMS),
           "other": t.get("other_compute", 0.0)}
    for k in BESIDE_TERMS:
        if t.get(k):
            out[k] = t[k]
    return out


def terms(price, sops, ops, n, step_ms, memory_peak_bytes=None, plan_total_ms=None):
    """The table as numbers: predicted and measured parts, the five metrics'
    values, and by how much each side misses its total (``plan_total_ms``: the
    plan file's ``search_cost_ms`` where there is one, else the price's own)."""
    pred, meas = predicted_ms(price), measured_ms(sops, ops, n)
    hidden = price.get("basis", {}).get("hidden_terms", ["dp_hidden", "tp_hidden"])
    total = plan_total_ms or price.get("basis", {}).get("total_ms") or sum(
        v for k, v in price["time_ms"].items() if k not in hidden)
    out = {
        "predicted": pred, "measured": meas, "predicted_total_ms": total, "step_ms": step_ms,
        # predicted parts on the critical path against the plan's total; measured parts
        # (device 0's profiled steps) against the window's median step span
        "predicted_miss_ms": total - sum(v for k, v in pred.items() if k not in hidden),
        "measured_miss_ms": step_ms - sum(meas.values()),
        "compute_ratio": pred["compute"] / meas["compute"] if meas["compute"] else None,
        "comm_ratio": pred["comm"] / meas["comm"] if meas["comm"] else None,
        "other_ratio": pred["other"] / meas["other"] if meas["other"] else None,
        "unpriced_share": 100.0 * (meas["optimizer"] + meas["unscoped"] + meas["idle"]) / step_ms,
        "mem_ratio": None,
    }
    if memory_peak_bytes and price.get("memory_mb"):
        out["mem_ratio"] = sum(price["memory_mb"].values()) * 1e6 / memory_peak_bytes
    return out


def _table(ctx, price, t):
    say, pred, meas = ctx["say"], t["predicted"], t["measured"]
    basis = price.get("basis", {})
    say(f"search terms: the plan as run, priced by {basis.get('source', '?')} on "
        f"{basis.get('costs', '?')} costs (compute at {basis.get('compute_tflops', '?')} TFLOP/s, "
        f"bandwidths from defaults: {basis.get('fallback_bandwidths')}, overlap_coe "
        f"{basis.get('overlap_coe')}); device 0, mean of {ctx['n_profiled']} profiled steps")
    priced = [sp["end"] - sp["start"] for sp in ctx.get("setup_spans", ()) if sp["name"] == "plan_price"]
    if priced:
        say(f"  the trainer's span plan_price (inside build_runtime): {sum(priced) * 1e3:.3f} ms of set-up")
    say(f"  {'term':22s} {'predicted ms':>13s} {'measured ms':>12s} {'ratio':>7s}")
    for part, terms_ in (("compute", COMPUTE_TERMS), ("comm", COMM_TERMS),
                         ("other", ("other_compute",))):
        ratio = f"{pred[part] / meas[part]:7.3f}" if meas[part] else "    n/a"
        say(f"  {part:22s} {pred[part]:13.3f} {meas[part]:12.3f} {ratio}   <- "
            + " + ".join(f"{k} {price['time_ms'].get(k, 0.0):.3f}" for k in terms_))
    for part in ("optimizer", "unscoped", "idle"):
        beside = f"   (pp_bubble {pred['pp_bubble']:.3f} predicted)" if (
            part == "idle" and "pp_bubble" in pred) else ""
        say(f"  {part:22s} {'unpriced':>13s} {meas[part]:12.3f}{beside}")
    for k in ("dp_hidden", "tp_hidden", "pipeline_coupled"):
        if k in pred:
            say(f"  {k:22s} {pred[k]:13.3f} {'':>12s}           <- "
                + ("believed hidden, in no ratio" if k != "pipeline_coupled" else "in no ratio"))
    say(f"  {'total':22s} {t['predicted_total_ms']:13.3f} {t['step_ms']:12.3f} "
        f"{t['predicted_total_ms'] / t['step_ms']:7.3f}   predicted parts miss the plan's total by "
        f"{t['predicted_miss_ms']:.3f} ms, measured parts miss step_ms_p50 by "
        f"{t['measured_miss_ms']:.3f} ms")


def of_ctx(ctx):
    """``terms`` of the traced run, once a run (the table is printed then), or None."""
    if "_search_terms" not in ctx:
        out = None
        price = plan_price()
        sops = scoped.device0(ctx) if price else None
        if sops is not None and ctx.get("step_s"):
            out = terms(price, sops, xplane.first_device(ctx["trace"]), ctx["n_profiled"],
                        percentile(ctx["step_s"], 50) * 1e3, ctx.get("memory_peak_bytes"),
                        (ctx.get("plan") or {}).get("search_cost_ms"))
            out["price"] = price
            _table(ctx, price, out)
            doc = (ctx.get("plan") or {}).get("search_price")
            if doc:
                same = all(doc.get(k) == price.get(k) for k in ("time_ms", "volume_mb", "memory_mb"))
                ctx["say"]("  the plan file's search_price and the trainer's plan_price "
                           + ("agree term for term" if same else "DIFFER")
                           + f" (source {price.get('basis', {}).get('source')})")
        ctx["_search_terms"] = out
    return ctx["_search_terms"]
