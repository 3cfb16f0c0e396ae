"""Share of device 0's busy time whose operation carries one of the program's
scopes.  Prints the step's device time by second-level scope, forward and
backward apart, and the ten largest operations without a scope."""

from benchmark.lib import scoped, xplane

NAME, UNIT, BETTER, SOURCE = "scope_coverage", "%", "higher", "device_trace"
LAYER, MOVES = "model step", "tokens_per_s_per_chip"


def compute(ctx):
    sops = scoped.device0(ctx)
    if sops is None:
        return None
    n, say = ctx["n_profiled"], ctx["say"]
    phases = scoped.phase_ns(sops)
    total = sum(phases.values())
    busy = xplane.busy_ns(xplane.first_device(ctx["trace"]))
    say("device 0 by phase, ms a step: " + ", ".join(
        f"{k} {v / 1e6 / n:.3f}" for k, v in phases.items())
        + f"; sum {total / 1e6 / n:.3f}, busy {busy / 1e6 / n:.3f}")
    table = scoped.second_level_ns(sops)
    for scope in sorted({k[0] for k in table}, key=lambda s: -sum(
            v for k, v in table.items() if k[0] == s)):
        row = {ph: table.get((scope, ph), 0.0) / 1e6 / n
               for ph in ("forward", "backward", "optimizer", "unscoped")}
        say(f"  scope {scope}: " + ", ".join(f"{ph} {v:.3f}" for ph, v in row.items() if v))
    for key, ns, calls in scoped.top_unscoped(sops):
        say(f"  unscoped {ns / 1e6 / n:.3f} ms a step in {calls / n:g} calls: {key[:200]}")
    return 100.0 * (total - phases["unscoped"]) / total if total else None
