"""What PR 67 adds to the benchmark, on the CPU: the reader of
``moe_layout_ms_per_step`` on a hand-made scoped step, its silence where a program
has no such scope (a dense model, a parent before the scope), its declaration for
the two training cells with a routed layer, and the scope itself in the program's
lowered text.  No number here is a device number.

Two accepted cases pin the manifest as it stood before this entry was appended
(tests/conftest.py marks them strict xfail: only a PR of kind ``benchmark`` may edit the
files they live in); their bodies stand here WHOLE, over the originals' own constants
(their modules loaded from their files), with the one clause amended each."""

import importlib.util
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark.lib import harness, scoped  # noqa: E402

NAME = "moe_layout_ms_per_step"
CELLS = ["olmoe-1b-7b_s4096", "qwen3-next-80b-a3b_s4096"]


@pytest.fixture(scope="module")
def metric():
    path = os.path.join(REPO, "benchmark", "metrics", NAME + ".py")
    spec = importlib.util.spec_from_file_location("_t_" + NAME, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _op(start, end, op_name):
    return scoped.ScopedOp(float(start), float(end), "fusion.1", "fusion:kLoop", op_name)


J = "jit(train_step)/"
#: one step by hand: 9 under the layout forward, 14 backward (the replay under full-layer
#: recomputation repeats its path), and work of the MLP, the dispatch and the step beside it
HAND = [
    _op(0, 4, J + "jvp(layer_0)/mlp/dispatch/layout/reduce_sum:"),
    _op(4, 9, J + "jvp(layer_0)/mlp/dispatch/layout/scatter:"),
    _op(9, 20, J + "jvp(layer_0)/mlp/dispatch/top_k:"),
    _op(20, 30, J + "jvp(layer_0)/mlp/experts/moe_gmm/pallas_call:"),
    _op(30, 37, J + "transpose(jvp(layer_1))/jvp(layer_1)/checkpoint/mlp/dispatch/layout/eq:"),
    _op(37, 44, J + "transpose(jvp(layer_1))/checkpoint/mlp/dispatch/jit(layout)/cumsum:"),
    _op(44, 50, J + "transpose(jvp(layer_1))/mlp/dispatch/gather:"),
    _op(50, 60, J + "jvp(layer_0)/attn/layout/mul:"),  # a scope of that name elsewhere is not it
    _op(60, 70, J + "optimizer/layout/add:"),
]


def _ctx(sops, said, n=1):
    return {"_scoped_device0": sops, "n_profiled": n, "say": said.append}


def test_reader_sums_the_layout_s_operations_forward_and_backward(metric):
    said = []
    assert metric.compute(_ctx(HAND, said)) == pytest.approx(23 / 1e6)
    assert metric.split_ns(HAND) == {"forward": 9.0, "backward": 14.0}
    assert metric.compute(_ctx(HAND, [], n=2)) == pytest.approx(11.5 / 1e6)
    assert "moe layout: forward" in said[0] and "backward" in said[0]


@pytest.mark.parametrize("op_name,inside", [
    (J + "jvp(layer_3)/mlp/dispatch/layout/scatter:", True),
    (J + "transpose(jvp(layer_3))/jvp(layer_3)/checkpoint/mlp/dispatch/layout/eq", True),
    (J + "jvp(layer_3)/mlp/dispatch/sort:", False),
    (J + "jvp(layer_3)/mlp/layout/dispatch/sort:", False),
    (J + "jvp(layer_3)/attn/dispatch/layout/sort:", False),
    ("", False),
])
def test_the_path_is_mlp_then_dispatch_then_layout(metric, op_name, inside):
    assert metric.under_layout(op_name) is inside


def test_reader_leaves_itself_out_without_the_scope(metric):
    """A parent before the scope (``dispatch`` without ``layout``), a dense model, no trace."""
    parent = [_op(0, 10, J + "jvp(layer_0)/mlp/dispatch/sort:"),
              _op(10, 20, J + "transpose(jvp(layer_0))/mlp/dispatch/gather:")]
    dense = [_op(0, 10, J + "jvp(layer_0)/mlp/dot_general:"), _op(10, 20, J + "jvp(head)/mul:")]
    for sops in (parent, dense, None):
        assert metric.compute(_ctx(sops, [])) is None


def test_metric_is_declared_for_the_two_training_cells_with_a_routed_layer(metric):
    manifest = harness.load_manifest(REPO)
    entry = {m["name"]: m for m in manifest["per_layer"]}[NAME]
    assert entry["workloads"] == CELLS
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"], entry["moves"]) == (
        metric.UNIT, metric.BETTER, metric.SOURCE, metric.LAYER, metric.MOVES)
    assert entry["layer"] in {m["layer"] for m in manifest["per_layer"][:30]}  # a layer it has
    moved = {m["name"]: m for m in manifest["end_to_end"]}[entry["moves"]]
    assert set(CELLS) <= set(moved["workloads"])
    assert NAME in [m.NAME for m in harness.discover_metrics(REPO)]


@pytest.mark.parametrize("share", [(0, 1), (1, 2)], ids=["all_held", "held_share"])
def test_the_program_opens_the_scope_inside_dispatch(metric, share):
    """`moe._topk_local`'s lowered text: the layout's operations carry
    ``dispatch/layout``, which is what the reader finds on the chip."""
    import re

    import jax
    import jax.numpy as jnp

    from galvatron_tpu.models import moe
    from galvatron_tpu.models.modeling import ModelConfig

    cfg = ModelConfig(vocab_size=64, hidden_size=32, num_layers=1, num_heads=4, ffn_dim=64,
                      moe_ffn_dim=32, max_seq_len=16, dtype=jnp.float32, act_fn="swiglu",
                      moe_experts=8, moe_router="softmax_topk", moe_top_k=2, moe_share=share)
    p = moe.init_moe_params(jax.random.key(0), cfg)
    x = jnp.ones((2, 8, 32), jnp.float32)

    def block(x_, p_):
        with jax.named_scope("mlp"):
            return moe.moe_topk_block(x_, p_, cfg)[0]

    text = jax.jit(block).lower(x, p).as_text(debug_info=True)
    names = set(re.findall(r'"(jit\(block\)/[^"]*)"', text))
    inside = [n for n in names if metric.under_layout(n)]
    assert inside and any("scatter" in n for n in inside)
    assert not any("sort" in n.rsplit("/", 1)[-1] for n in names)


# -- the two accepted cases that pinned the manifest before this entry, whole ---------------


def _accepted(name):
    """An accepted test file as a module: its constants."""
    spec = importlib.util.spec_from_file_location("_held67_" + name, os.path.join(HERE, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_dots3_the_cell_joins_the_manifest_by_appends():
    """`test_benchmark_dots3.py::test_the_cell_joins_the_manifest_by_appends`, amended:
    PR 65's five readers are followed by this PR's one (89 in all)."""
    d3 = _accepted("test_benchmark_dots3")
    manifest = harness.load_manifest(REPO)
    names = [w["name"] for w in manifest["workloads"]]
    assert names[-1] == d3.CELL and len(names) == 13
    assert [n for n in names if n in d3.SERVING_BEFORE] == d3.SERVING_BEFORE
    cell = manifest["workloads"][-1]
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("dots3-note-prev", d3.TRAFFIC, 1)
    assert len(cell["why"]) <= 200 and len(manifest["configs"][-1]["why"]) <= 200
    assert manifest["configs"][-1]["name"] == "dots3-note-prev" and len(manifest["configs"]) == 10
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 2  # 2 of 13: no more
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert e2e["serve_tokens_per_s_per_chip"]["workloads"] == d3.SERVING_BEFORE + [d3.CELL]
    assert d3.CELL not in e2e["tokens_per_s_per_chip"]["workloads"]
    assert "workloads" not in e2e["setup_s"] and manifest["run_seconds"] == 51
    per = [m["name"] for m in manifest["per_layer"]]
    # THE amended clause (the original: ``per[-5:] == NEW_METRICS and len(per) == 88``)
    assert per[-6:] == d3.NEW_METRICS + [NAME] and len(per) == 89
    # no list of a per-layer metric names the new cell: its readers are the unlisted ones
    assert not [m["name"] for m in manifest["per_layer"] if d3.CELL in m.get("workloads", [])]
    # a full check fits the driver's budget at one more cell
    cells = len(names)
    assert (2 + 14 * cells) * (manifest["run_seconds"] + 60) + 2 * 90 * cells + 1200 <= 43200


def test_qwen3_next_the_cell_joins_no_list_but_its_own_metrics_the_layouts_and_the_rate():
    """`test_benchmark_qwen3_next.py::
    test_the_cell_joins_no_list_but_its_own_metrics_and_the_rate`, amended: the lists that
    name the cell are PR 47's four, the rate, and this PR's one (the four ``moe_*`` lists of
    PR 28 stay as they are: B10 n 6, a ``benchmark`` PR's)."""
    q3 = _accepted("test_benchmark_qwen3_next")
    manifest = harness.load_manifest(REPO)
    names = [w["name"] for w in manifest["workloads"]]
    assert names.index(q3.CELL) == 6 and [c["name"] for c in manifest["configs"]].index(
        "qwen3-next-80b-a3b") == 4
    listed = sorted(e["name"] for e in manifest["end_to_end"] + manifest["per_layer"]
                    if q3.CELL in e.get("workloads", []))
    # THE amended clause (the original: without ``[NAME]``)
    assert listed == sorted(q3.NEW_METRICS + ["tokens_per_s_per_chip"] + [NAME])
    rate = next(e for e in manifest["end_to_end"] if e["name"] == "tokens_per_s_per_chip")
    assert rate["workloads"].index(q3.CELL) == 5  # appended, nothing before it moved


def test_every_marked_case_has_its_whole_copy_here():
    """tests/conftest.py's list and this file, one for one: a case marked there without its
    copy here would be a test switched off."""
    sys.path.insert(0, os.path.dirname(HERE))
    import conftest

    copies = {
        "test_benchmark_dots3.py::test_the_cell_joins_the_manifest_by_appends":
            test_dots3_the_cell_joins_the_manifest_by_appends,
        "test_benchmark_qwen3_next.py::"
        "test_the_cell_joins_no_list_but_its_own_metrics_and_the_rate":
            test_qwen3_next_the_cell_joins_no_list_but_its_own_metrics_the_layouts_and_the_rate,
    }
    marked = {node.split("tests/benchmark/")[1]
              for node in conftest._PINNED_TO_THE_MANIFEST_BEFORE_PR_67}
    assert marked == set(copies) and all(callable(f) for f in copies.values())
