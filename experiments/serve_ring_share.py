"""One traced run of a windowed serving cell that also prints what the benchmark has
no reader for yet (PERF.md section 7): the ring key blocks the chunks of the window's
`prefill` spans read, of the rings' (`kv_window_chunk_blocks_read` /
`kv_window_chunk_blocks`, PR 55), and one `decode` span's `kv_*` counters. Run from
the root of the tree to measure, through the chip tool:

    python3 experiments/serve_ring_share.py --workload smallthinker-21b-a3b_serve_long_above_knee \\
        --seed N --seconds 51 --trace 1

It is `benchmark/run.py` with `harness.collect_per_layer` wrapped; the result line is
the benchmark's own."""

import importlib.util
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    from benchmark.lib import harness

    spec = importlib.util.spec_from_file_location("bench_run", os.path.join(ROOT, "benchmark", "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    collect = harness.collect_per_layer

    def wrapped(root, name, ctx, result):
        chunks = [s["args"] for s in ctx["spans"]
                  if s["name"] == "prefill" and "kv_window_chunk_blocks" in s["args"]]
        read = sum(a["kv_window_chunk_blocks_read"] for a in chunks)
        of = sum(a["kv_window_chunk_blocks"] for a in chunks)
        harness.say(f"ring key blocks read by the chunks of the window's {len(chunks)} prefill "
                    f"spans: {read} of {of} = {read / max(of, 1):.4f}")
        steps = [s["args"] for s in ctx["spans"]
                 if s["name"] == "decode" and "kv_full_read_positions" in s["args"]]
        if steps:
            mid = steps[len(steps) // 2]
            harness.say("a decode span's counters: " + ", ".join(
                f"{k} {mid[k]}" for k in sorted(mid) if k.startswith("kv_")))
        return collect(root, name, ctx, result)

    harness.collect_per_layer = wrapped
    return run.main()


if __name__ == "__main__":
    sys.exit(main())
