"""What a model's test file needs to hold the model to its published reference, written
once: seeded weights, the comparison, the reference's compiled program, the forwards
COMPILED as the serving engine compiles them, the loop of cached forwards, the engine,
the plan, the refusal table's runner.

A model's file (``tests/test_<model>.py``) keeps what is the model's own: ``small_cfg``,
``ref_cfg`` (the mapping onto the reference's configuration) and the tests of its own
mechanisms.  Nothing here runs a whole model eagerly: on the CPU an eager forward
dispatches every operation of every layer by itself (minutes a test), so the forwards are
`jax.jit` programs with ``cfg`` static, and a second call with the same ``cfg`` and shapes
reuses the first one's program (tests/test_stack_harness.py holds that).

Not collected (underscore), as ``tests/_cached_forward_reference.py`` is.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from galvatron_tpu.core.strategy import HybridParallelConfig
from galvatron_tpu.models import generation, modeling
from galvatron_tpu.ops import pallas_common
from galvatron_tpu.parallel.hybrid import build_runtime
from galvatron_tpu.parallel.mesh import build_mesh

# -- weights, rows, the comparison --------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _seeded(cfg, seed, batch, length, spread):  # (cached: arguments by position)
    params = modeling.init_model_params(jax.random.key(seed), cfg)
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(seed + 1), len(leaves))
    leaves = [a + spread * jax.random.normal(k, a.shape, a.dtype) if a.ndim == 1 else a
              for a, k in zip(leaves, keys)]
    rows = jax.random.randint(jax.random.key(seed + 2), (batch, length), 0, cfg.vocab_size,
                              jnp.int32)
    return jax.tree.unflatten(tree, leaves), rows


def seeded(cfg, seed=0, batch=2, length=None, spread=0.2, targets=False):
    """Parameters with every vector (norm scales, biases, decays) moved off its initial
    value by ``spread``, so that one the program ignores shows, and ``batch`` rows of
    ``length`` (None: ``cfg.max_seq_len``) tokens, with ``targets`` one more (a training
    batch: the last position's target).  Made once a signature; the tree is a copy a call
    (a test may replace a leaf of its own)."""
    params, rows = _seeded(cfg, seed, batch, (length or cfg.max_seq_len) + targets, spread)
    return jax.tree.map(lambda a: a, params), rows


def worst(got, want, floor=1.0):
    """The largest difference, as a share of ``want``'s largest magnitude (of ``floor``
    where that is larger: logits; 0 for gradients and blocks far under 1)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.max(np.abs(got - want)) / max(np.max(np.abs(want)), floor, 1e-30)


def close(got, want, tol, floor=1.0):
    err = worst(got, want, floor)
    assert err <= tol, f"largest difference {err:.3e} of the largest magnitude, bound {tol:.0e}"


def close_by_leaf(got, want, tol, floor=1.0):
    """`close` leaf by leaf of two trees, the failing leaf named."""
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)):
        try:
            close(g, w, tol, floor)
        except AssertionError as e:
            raise AssertionError(f"{jax.tree_util.keystr(path)}: {e}") from None


@pytest.fixture
def highest_precision():
    """float32 products in float32 (a file: ``pytestmark = pytest.mark.usefixtures(...)``)."""
    with jax.default_matmul_precision("highest"):
        yield


# -- the reference ------------------------------------------------------------------------


class Reference:
    """The published reference ``arch`` (a module of ``benchmark/references``) at its
    configuration ``rc``, as compiled programs of the PROGRAM's parameter tree:
    ``logits(params, rows)`` and ``objective(params, rows)`` -> (cross entropy of
    ``rows[:, 1:]`` given ``rows[:, :-1]``, the auxiliary loss or 0.0), float32 at
    ``highest``."""

    def __init__(self, arch, rc):
        def logits(params, rows):
            with jax.default_matmul_precision("highest"):
                return arch.logits(arch.published_weights(params, rc), rows, rc)

        def objective(params, rows):
            with jax.default_matmul_precision("highest"):
                w = arch.published_weights(jax.tree.map(lambda a: a.astype(jnp.float32), params), rc)
                logp = jax.nn.log_softmax(arch.logits(w, rows[:, :-1], rc), axis=-1)
                ce = -jnp.mean(jnp.take_along_axis(logp, rows[:, 1:, None], axis=-1))
                aux = arch.aux_loss(w, rows[:, :-1], rc) if hasattr(arch, "aux_loss") else 0.0
                return ce, aux

        self.logits = jax.jit(logits)
        self.objective = jax.jit(objective)


@functools.lru_cache(maxsize=None)
def reference(arch, ref_cfg, cfg, share=None):
    """`Reference` of ``arch`` at ``ref_cfg(cfg, share)`` (the file's mapping), ONE a
    ``(cfg, share)``: a second call hands back the first one's compiled programs."""
    return Reference(arch, ref_cfg(cfg, share))


# -- the program, compiled ------------------------------------------------------------------

#: `modeling.forward`, `forward_with_stats`, `lm_loss` and `moe_loss_sum` with ``cfg`` static
forward = jax.jit(modeling.forward, static_argnames=("cfg",))
forward_with_stats = jax.jit(modeling.forward_with_stats, static_argnames=("cfg",))
lm_loss = jax.jit(modeling.lm_loss, static_argnames=("cfg",))
moe_loss_sum = jax.jit(modeling.moe_loss_sum, static_argnames=("cfg",))


def loss_and_gradients(fn, params):
    """``fn(params)`` and its gradient by every leaf, as one compiled program."""
    return jax.jit(jax.value_and_grad(fn))(params)


def every_gradient_matches(params, rows, cfg, ref, tol):
    """The gradient of the training objective by every parameter (the cross entropy of
    ``rows``, plus ``cfg.moe_aux_coef`` times the auxiliary loss of a dropless expert
    model), the program's against the `Reference` ``ref``'s: no leaf of the reference's is
    zero, and each of the program's is within ``tol`` of its largest magnitude -> the
    program's gradients."""
    def program(p):
        if not cfg.moe_dropless:
            return modeling.lm_loss(p, rows, cfg)
        s, n, aux = modeling.moe_loss_sum(p, rows, cfg)
        return s / n + cfg.moe_aux_coef * aux["moe_aux_loss"]

    def plain(p):
        ce, aux = ref.objective(p, rows)
        return ce + cfg.moe_aux_coef * aux if cfg.moe_dropless else ce

    got, want = loss_and_gradients(program, params)[1], loss_and_gradients(plain, params)[1]
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for path, w in jax.tree.leaves_with_path(want):
        assert float(jnp.abs(w).max()) > 0, f"{jax.tree_util.keystr(path)}: reference gradient is zero"
    close_by_leaf(got, want, tol, floor=0.0)
    return got


def bf16_fails_the_tolerance(cfg, ref_logits, tol, length=40):
    """The float32 comparison has power: with the matrices and the arithmetic in bfloat16
    (8 mantissa bits where the configuration states 24) the logits land outside ``tol``."""
    params, rows = seeded(cfg, length=length)
    low = jax.tree.map(lambda a: a.astype(jnp.bfloat16) if a.ndim > 1 else a, params)
    got = forward(low, rows, cfg.replace(dtype=jnp.bfloat16))
    assert worst(got.astype(jnp.float32), ref_logits(params, rows, cfg)) > tol


def bf16_stays_within(cfg, params, rows, ref_logits, bf16_tol, f32_tol):
    """``cfg``'s bfloat16 compute against the float32 reference: inside what bfloat16
    warrants, and outside the float32 tolerance (a bf16 run inside it: the tolerance has no
    power)."""
    err = worst(forward(params, rows, cfg).astype(jnp.float32), ref_logits(params, rows, cfg), 0.0)
    assert f32_tol < err <= bf16_tol, err


def lockstep_generation_is_greedy(cfg, ref_logits, max_new_tokens, lengths=(20, 14)):
    """`generation.generate` over the stack's cache, two rows whose prompts end at
    ``lengths``: each generated token is the reference's argmax given what came before."""
    longest = max(lengths)
    params, rows = seeded(cfg, batch=len(lengths), length=longest)
    out = np.asarray(generation.generate(
        params, rows, jnp.asarray(lengths), cfg, jax.random.key(0),
        max_new_tokens=max_new_tokens, min_prompt_len=min(lengths)))
    assert out.shape == (len(lengths), longest + max_new_tokens)
    picks = np.asarray(ref_logits(params, out[:, :-1], cfg)).argmax(-1)
    for b, n in enumerate(lengths):
        assert np.array_equal(out[b, :n], np.asarray(rows[b, :n]))
        assert np.array_equal(out[b, n:], picks[b, n - 1:])


# the two forwards the engine runs, jitted as `serving/engine.py` jits `_prefill_chunk` and
# `_decode_step` / `_decode_verify` (``cfg`` static; nothing donated: a test reads a cache twice)
@functools.partial(jax.jit, static_argnames=("cfg",))
def chunk_forward(params, cfg, cache, tokens, start, slot, last):
    return generation.forward_with_cache(params, tokens, cfg, cache, start, slot=slot, last=last)


@functools.partial(jax.jit, static_argnames=("cfg",))
def step_forward(params, cfg, cache, tokens, offsets):
    return generation.forward_with_cache(params, tokens, cfg, cache, offsets)


@pytest.fixture
def retraced(monkeypatch):
    """For a test that patches what a traced forward binds (a key block, the interpret
    switch, a planted fault): ``retraced()`` after patching drops the programs of
    `chunk_forward`, `step_forward` and `forward`, and the end of the test undoes the
    patches and drops them again, so that no later test meets a program traced under them."""
    def drop():
        for program in (chunk_forward, step_forward, forward):
            program.clear_cache()

    yield drop
    monkeypatch.undo()
    drop()


#: a pad row's token: its own, so that a pad row that reached a state or a logit would show
PAD = 7


def prefill(params, cfg, cache, slot, prompt, chunk=4):
    """``prompt`` into row ``slot`` in chunks padded to ``chunk``, as the engine's
    `_prefill_chunk` runs them (``last`` = the chunk's last real row) -> (logits of every
    prompt position, cache)."""
    out = []
    for start in range(0, len(prompt), chunk):
        n = min(chunk, len(prompt) - start)
        buf = np.full((1, chunk), PAD, np.int32)
        buf[0, :n] = prompt[start:start + n]
        lg, cache = chunk_forward(params, cfg, cache, jnp.asarray(buf), jnp.int32(start),
                                  jnp.int32(slot), jnp.int32(n - 1))
        out.append(np.asarray(lg[0, :n]))
    return np.concatenate(out), cache


def decode(params, cfg, cache, rows_at, slots=3, verify=0, steps=None):
    """Shared decode steps over all ``slots`` rows of ``cache``: ``rows_at`` {slot: (row of
    tokens, position, end)} decodes the row's tokens [position, end) in windows of ``1 +
    verify``, rows at their own depths; a slot out of use or at its end carries (0, 0).
    ``steps``: that many steps and no more (idle ones among them) in place of until every
    row is at its end -> ({slot: logits of its positions}, cache)."""
    out = {s: [] for s in rows_at}
    at = {s: pos for s, (_, pos, _) in rows_at.items()}
    width, done = 1 + verify, 0
    while (done < steps) if steps is not None else any(
            at[s] < end for s, (_, _, end) in rows_at.items()):
        toks, offs = np.zeros((slots, width), np.int32), np.zeros((slots,), np.int32)
        live = {s: min(width, end - at[s]) for s, (_, _, end) in rows_at.items() if at[s] < end}
        for s, n in live.items():
            toks[s, :n] = rows_at[s][0][at[s]:at[s] + n]
            offs[s] = at[s]
        lg, cache = step_forward(params, cfg, cache, jnp.asarray(toks), jnp.asarray(offs))
        for s, n in live.items():
            out[s].append(np.asarray(lg[s, :n]))
            at[s] += n
        done += 1
    return {s: np.concatenate(v) for s, v in out.items() if v}, cache


def through_the_cache(params, cfg, prompts, total, slots=3, chunk=4, verify=0, cache=None,
                      capacity=None):
    """`prefill` ``prompts`` ({slot: (row of tokens, prompt length)}) in chunks, then
    `decode` every slot to ``total[slot]`` positions in shared steps -> ({slot: logits of
    every position}, cache).  ``cache``: one handed in (a slot used again, not zeroed);
    None: a fresh one of ``slots`` rows of ``capacity`` (None: ``cfg.max_seq_len``)."""
    if cache is None:
        cache = generation.init_kv_cache(cfg, slots, capacity or cfg.max_seq_len,
                                         tokens=max(chunk, 1 + verify))
    out = {}
    for slot, (row, n) in prompts.items():
        out[slot], cache = prefill(params, cfg, cache, slot, row[:n], chunk)
    rows_at = {s: (row, n, total[s]) for s, (row, n) in prompts.items()}
    decoded, cache = decode(params, cfg, cache, rows_at, slots, verify)
    return {s: np.concatenate([out[s], decoded[s]]) if s in decoded else out[s]
            for s in prompts}, cache


# -- the engine, the plan, the refusals ------------------------------------------------------


def engine(cfg, params, **kw):
    from galvatron_tpu.serving import Engine

    args = dict(num_slots=3, prefill_chunk=4, max_queue=64, eos_id=-1, pad_id=0, seed=0)
    args.update(kw)
    return Engine(params, cfg, **args)


def first_request_ids(monkeypatch):
    """Request ids from 0 until the test ends. An engine's draws are a function of (seed,
    request id, token index) and the ids a counter of the process, so a test that holds a
    nearly greedy draw to the arg-max hangs, without this, on how many requests the worker's
    earlier tests submitted."""
    import itertools

    from galvatron_tpu.serving import scheduler

    monkeypatch.setattr(scheduler, "_rid", itertools.count())


def serve(eng, prompts, max_new_tokens, traced=False):
    """``eng.generate`` of ``prompts``, then the engine closed -> (the served rows, its
    ``stats()``, and, ``traced``, what the complete spans the tracer kept meanwhile carry,
    by name: ``{"decode": [args, ...], "prefill": [...], ...}``)."""
    from galvatron_tpu.obs.tracing import tracer

    if traced:
        tracer.enable(capacity=1 << 13)
        tracer.clear()  # (the ring is the process's: another engine's spans may lie in it)
    spans = {}
    try:
        served = eng.generate(prompts, max_new_tokens=max_new_tokens)
        stats = eng.stats()
        for event in tracer.snapshot() if traced else ():
            if event.get("ph") == "X":
                spans.setdefault(event["name"], []).append(event["args"])
    finally:
        if traced:
            tracer.disable()
            tracer.clear()
        eng.close()
    return served, stats, spans


def generations(params, cfg, prompts, max_new_tokens):
    """What plain generation gives for each of ``prompts`` alone, greedy: what an engine
    that serves them together, in whatever slots and chunks, has to serve."""
    return [generation.generate_np(params, cfg, [p], max_new_tokens=max_new_tokens,
                                   length_bucket=1)[0] for p in prompts]


def plan(cfg, pp=1, **kw):
    return HybridParallelConfig.uniform(cfg.num_layers, pp=pp, **kw)


def refuses(table, small_cfg, seq_len=32, batch=4, devices=2):
    """The test of a ``REFUSALS`` table: rows (name, what `small_cfg` takes over its
    defaults, the uniform plan's fields or ``cfg -> plan``, the sentence's pattern);
    `build_runtime` refuses each by name on ``devices`` of the CPU mesh."""
    @pytest.mark.parametrize("name,over,fields,message", table, ids=[r[0] for r in table])
    def test_build_runtime_refuses_by_name(name, over, fields, message):
        cfg = small_cfg(**over)
        hp = fields(cfg) if callable(fields) else plan(cfg, mixed_precision="fp32", **fields)
        mesh, axes = build_mesh(pp=hp.pp, devices=jax.devices()[:devices])
        with pytest.raises(ValueError, match=message):
            build_runtime(cfg, hp, mesh=mesh, axes=axes, global_batch_size=batch, seq_len=seq_len)

    return test_build_runtime_refuses_by_name


def flat_losses(cfg, params, batches, adam):
    """The losses of a single-device AdamW loop over ``params`` held flat, ONE compiled
    loss-and-gradient program for all its steps: the trajectory a sharded or pipelined
    runtime started from the same parameters must track."""
    from galvatron_tpu.core.optim import adamw_update, init_opt_state

    step = jax.jit(jax.value_and_grad(lambda p, b: modeling.lm_loss(p, b, cfg)))
    opt, losses = init_opt_state(params), []
    for b in batches:
        loss, grads = step(params, b)
        params, opt = adamw_update(params, grads, opt, adam)
        losses.append(float(loss))
    return losses


def tracks_the_flat_trajectory(rt, state, flat, cfg, batches, adam, tol=5e-5):
    """``rt`` trained on ``batches`` from ``state``: every step's loss is `flat_losses`'
    from ``flat``, the same parameters as one device holds them."""
    want = flat_losses(cfg, flat, batches, adam)
    got = []
    for b in batches:
        state, loss = rt.train_step(state, b)
        got.append(float(loss))
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


def trains_on_one_device(cfg, steps, drop, batch=4):
    """`build_runtime` on one device under a uniform float32 plan, ``steps`` steps on one
    repeated batch: the runtime's loss is the model's before the first step, every loss is
    finite and the last is under the first by ``drop`` -> (the parameters before the first
    step, the state after the last), for what else a file holds of them."""
    from galvatron_tpu.core.optim import AdamConfig

    mesh, axes = build_mesh(pp=1, devices=jax.devices()[:1])
    rt = build_runtime(cfg, plan(cfg, mixed_precision="fp32"), mesh=mesh, axes=axes,
                       adam=AdamConfig(lr=3e-3),
                       global_batch_size=batch, seq_len=cfg.max_seq_len)
    state = rt.init_state(jax.random.key(0))
    rows = jax.random.randint(jax.random.key(1), (batch, cfg.max_seq_len + 1), 0, cfg.vocab_size,
                              jnp.int32)
    before = jax.tree.map(np.asarray, state["params"])
    want = lm_loss(state["params"], rows, cfg)
    assert float(rt.eval_loss(state, rt.shard_batch(rows))) == pytest.approx(float(want), rel=1e-5)
    losses = []
    for _ in range(steps):
        state, loss = rt.train_step(state, rt.shard_batch(rows))
        losses.append(float(loss))
    assert np.isfinite(losses).all() and losses[-1] < losses[0] - drop, losses
    return before, state


def one_device_loss_on_a_mesh(cfg, batch=8):
    """dp 8 under ZeRO-3 over the 8-device CPU mesh: the runtime's loss is the model's
    one-device loss of the same parameters."""
    mesh, axes = build_mesh(pp=1)
    rt = build_runtime(cfg, plan(cfg, dp_type="zero3", mixed_precision="fp32"), mesh=mesh, axes=axes,
                       global_batch_size=batch, seq_len=cfg.max_seq_len)
    state = rt.init_state(jax.random.key(0))
    rows = jax.random.randint(jax.random.key(1), (batch, cfg.max_seq_len + 1), 0, cfg.vocab_size,
                              jnp.int32)
    want = lm_loss(jax.tree.map(np.asarray, state["params"]), rows, cfg)
    assert float(rt.eval_loss(state, rt.shard_batch(rows))) == pytest.approx(float(want), rel=1e-4)


def cli_serve_parses(argv, expect):
    """``cli serve``'s flags ``argv`` -> the `ModelConfig`, whose fields ``expect`` names
    hold the values it gives."""
    from galvatron_tpu.core.arguments import initialize_galvatron, model_config_from_args

    cfg = model_config_from_args(initialize_galvatron("serve", argv))
    got = {name: getattr(cfg, name) for name in expect}
    assert got == expect
    return cfg


# -- kernels small enough to interpret, and a chip's answers --------------------------------


def small_tiles(monkeypatch, *modules, key_block=16):
    """``KEY_BLOCK`` of each of ``modules`` (the kernels' and their callers': 1,024 on the
    chip) at the tests' sizes, so that a slot of 64 positions is whole key blocks and the
    kernel, interpreted here, takes it."""
    for module in modules:
        monkeypatch.setattr(module, "KEY_BLOCK", key_block)


def on_a_chip(monkeypatch):
    """The kernels' ONE switch (`pallas_common.use_interpret`) as a chip answers it: for a
    test that asks which body a shape takes there (`scan_path`, `conv_path`, `chunk_path`,
    `decode_path`); the kernels themselves run here interpreted, or compile for a described
    v5e in tests/test_topology_aot.py."""
    monkeypatch.setattr(pallas_common, "use_interpret", lambda: False)
