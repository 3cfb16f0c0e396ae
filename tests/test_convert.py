"""HF checkpoint import: numerical parity with the HuggingFace LLaMA torch
forward (the reference's model layer wraps exactly these HF models with their
weights — models/llama_hf/train_dist.py builds LlamaForCausalLM and swaps
layers in place, so logit parity against HF IS parity against the reference's
model definition)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

from tests._stack_harness import forward
from galvatron_tpu.models.convert import (
    config_from_hf_llama,
    from_hf_llama,
    load_hf_llama,
)


def tiny_hf(num_kv_heads=4):
    cfg = transformers.LlamaConfig(
        vocab_size=128,
        hidden_size=64,
        intermediate_size=112,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=num_kv_heads,
        max_position_embeddings=64,
        rms_norm_eps=1e-6,
    )
    torch.manual_seed(0)
    return transformers.LlamaForCausalLM(cfg).eval()


def logits_parity(hf_model, atol=2e-4):
    cfg = config_from_hf_llama(hf_model.config).replace(
        dtype=jnp.float32, param_dtype=jnp.float32, attn_impl="xla"
    )
    params = from_hf_llama(hf_model, cfg)
    tokens = np.random.RandomState(0).randint(0, cfg.vocab_size, (2, 16))
    with torch.no_grad():
        ref = hf_model(torch.tensor(tokens)).logits.numpy()
    ours = np.asarray(forward(params, jnp.asarray(tokens, jnp.int32), cfg))
    np.testing.assert_allclose(ours, ref, rtol=2e-4, atol=atol)


def test_hf_llama_logit_parity_mha():
    logits_parity(tiny_hf(num_kv_heads=4))


def test_hf_llama_logit_parity_gqa():
    """GQA (kv_heads < heads) exercises the interleaved fused-QKV packing."""
    logits_parity(tiny_hf(num_kv_heads=2))


def test_load_hf_llama_roundtrip(tmp_path):
    hf = tiny_hf()
    hf.save_pretrained(tmp_path / "ckpt")
    params, cfg = load_hf_llama(str(tmp_path / "ckpt"))
    assert cfg.hidden_size == 64 and cfg.num_layers == 2
    assert params["layers"][0]["attn"]["wqkv"].shape == (64, 3, 64)


def test_load_hf_rejects_unsupported_arch(tmp_path):
    bloom = transformers.BloomForCausalLM(
        transformers.BloomConfig(
            hidden_size=32, n_layer=1, n_head=2, vocab_size=64,
        )
    )
    bloom.save_pretrained(tmp_path / "bloom")
    with pytest.raises(ValueError, match="LLaMA-architecture"):
        load_hf_llama(str(tmp_path / "bloom"))


def test_hf_opt_logit_parity():
    """OPT import: separate-q/k/v packing, +2 position offset baked into the
    table, ReLU MLP — logit parity vs the HF torch forward."""
    from galvatron_tpu.models.convert import config_from_hf_opt, from_hf_opt

    hf_cfg = transformers.OPTConfig(
        hidden_size=48, num_hidden_layers=2, num_attention_heads=4,
        ffn_dim=96, vocab_size=96, max_position_embeddings=32,
        word_embed_proj_dim=48, activation_function="relu",
    )
    torch.manual_seed(3)
    hf = transformers.OPTForCausalLM(hf_cfg).eval()
    cfg = config_from_hf_opt(hf_cfg).replace(
        dtype=jnp.float32, param_dtype=jnp.float32, attn_impl="xla"
    )
    params = from_hf_opt(hf, cfg)
    tokens = np.random.RandomState(3).randint(0, 96, (2, 16))
    with torch.no_grad():
        ref = hf(torch.tensor(tokens)).logits.numpy()
    ours = np.asarray(forward(params, jnp.asarray(tokens, jnp.int32), cfg))
    np.testing.assert_allclose(ours, ref, rtol=2e-4, atol=2e-4)


def test_hf_opt_through_dispatcher(tmp_path):
    """OPT checkpoint → load_hf_checkpoint → runtime trains."""
    from galvatron_tpu.core.optim import AdamConfig
    from galvatron_tpu.core.strategy import HybridParallelConfig
    from galvatron_tpu.parallel.hybrid import build_runtime

    hf = transformers.OPTForCausalLM(
        transformers.OPTConfig(
            hidden_size=48, num_hidden_layers=2, num_attention_heads=4,
            ffn_dim=96, vocab_size=96, max_position_embeddings=32,
            word_embed_proj_dim=48, activation_function="relu",
        )
    )
    hf.save_pretrained(tmp_path / "opt")
    params, cfg = load_hf_llama(str(tmp_path / "opt"))
    cfg = cfg.replace(dtype=jnp.float32, param_dtype=jnp.float32)
    hp = HybridParallelConfig.uniform(2, tp=2, vocab_tp=2, mixed_precision="fp32")
    rt = build_runtime(cfg, hp, adam=AdamConfig(lr=1e-3), global_batch_size=8, seq_len=16)
    state = rt.init_state_from(params)
    batch = jnp.asarray(np.random.RandomState(0).randint(0, 96, (8, 17)), jnp.int32)
    state, l1 = rt.train_step(state, batch)
    state, l2 = rt.train_step(state, batch)
    assert np.isfinite(float(l2)) and float(l2) < float(l1)


def test_to_hf_gpt2_roundtrip():
    """Export half of the GPT-2 round trip: our params → HF state dict →
    GPT2LMHeadModel forward matches our forward."""
    from galvatron_tpu.models.convert import (
        config_from_hf_gpt2, from_hf_gpt2, to_hf_gpt2,
    )

    hf_cfg = transformers.GPT2Config(
        vocab_size=96, n_embd=48, n_layer=2, n_head=4, n_positions=32
    )
    torch.manual_seed(4)
    hf = transformers.GPT2LMHeadModel(hf_cfg).eval()
    cfg = config_from_hf_gpt2(hf_cfg).replace(
        dtype=jnp.float32, param_dtype=jnp.float32, attn_impl="xla"
    )
    params = from_hf_gpt2(hf, cfg)
    sd = to_hf_gpt2(params, cfg)
    hf2 = transformers.GPT2LMHeadModel(hf_cfg).eval()
    missing, unexpected = hf2.load_state_dict(
        {k: torch.tensor(v) for k, v in sd.items()}, strict=False
    )
    assert not unexpected, unexpected
    # attn.bias/masked_bias buffers are autogenerated; no weights may be missing
    assert all("attn.bias" in m or "masked_bias" in m for m in missing), missing
    tokens = np.random.RandomState(4).randint(0, 96, (2, 16))
    with torch.no_grad():
        a = hf(torch.tensor(tokens)).logits.numpy()
        b = hf2(torch.tensor(tokens)).logits.numpy()
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_hf_gpt2_logit_parity():
    """GPT-2 import: biases + blocked c_attn mapping, logit parity vs the HF
    torch forward (the reference's gpt_hf family wraps this exact model)."""
    from galvatron_tpu.models.convert import config_from_hf_gpt2, from_hf_gpt2

    hf_cfg = transformers.GPT2Config(
        vocab_size=96, n_embd=48, n_layer=2, n_head=4, n_positions=32
    )
    torch.manual_seed(2)
    hf = transformers.GPT2LMHeadModel(hf_cfg).eval()
    cfg = config_from_hf_gpt2(hf_cfg).replace(
        dtype=jnp.float32, param_dtype=jnp.float32, attn_impl="xla"
    )
    params = from_hf_gpt2(hf, cfg)
    tokens = np.random.RandomState(2).randint(0, 96, (2, 16))
    with torch.no_grad():
        ref = hf(torch.tensor(tokens)).logits.numpy()
    ours = np.asarray(forward(params, jnp.asarray(tokens, jnp.int32), cfg))
    np.testing.assert_allclose(ours, ref, rtol=2e-4, atol=2e-4)


def test_load_hf_gpt2_through_runtime(tmp_path):
    """GPT-2 checkpoint → dispatcher → hybrid runtime trains (bias params
    shard and update end to end)."""
    from galvatron_tpu.core.optim import AdamConfig
    from galvatron_tpu.core.strategy import HybridParallelConfig, LayerStrategy
    from galvatron_tpu.models.convert import load_hf_checkpoint
    from galvatron_tpu.parallel.hybrid import build_runtime

    hf = transformers.GPT2LMHeadModel(
        transformers.GPT2Config(vocab_size=96, n_embd=48, n_layer=2, n_head=4,
                                n_positions=32)
    )
    hf.save_pretrained(tmp_path / "gpt2")
    params, cfg = load_hf_checkpoint(str(tmp_path / "gpt2"))
    cfg = cfg.replace(dtype=jnp.float32, param_dtype=jnp.float32, attn_impl="xla")
    hp = HybridParallelConfig(
        layer_strategies=[LayerStrategy(tp=2, dp_type="zero3")] * 2,
        mixed_precision="fp32",
    )
    rt = build_runtime(cfg, hp, adam=AdamConfig(lr=1e-3), global_batch_size=8, seq_len=16)
    state = rt.init_state_from(params)
    tokens = jnp.asarray(np.random.RandomState(3).randint(0, 96, (8, 17)), jnp.int32)
    l0 = float(rt.eval_loss(state, tokens))
    for _ in range(4):
        state, loss = rt.train_step(state, tokens)
    assert float(loss) < l0  # biases train too


def hf_ce_loss(hf_model, tokens):
    """Reference next-token cross entropy from the HF torch forward."""
    x = torch.tensor(tokens)
    with torch.no_grad():
        logits = hf_model(x[:, :-1]).logits
    return float(
        torch.nn.functional.cross_entropy(
            logits.reshape(-1, logits.shape[-1]), x[:, 1:].reshape(-1)
        )
    )


def runtime_loss_parity(hp_kwargs, n_layers=2, atol=2e-4):
    from galvatron_tpu.core.optim import AdamConfig
    from galvatron_tpu.core.strategy import HybridParallelConfig, LayerStrategy
    from galvatron_tpu.parallel.hybrid import build_runtime

    cfg_hf = transformers.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=112,
        num_hidden_layers=n_layers, num_attention_heads=4, num_key_value_heads=4,
        max_position_embeddings=64,
    )
    torch.manual_seed(1)
    hf = transformers.LlamaForCausalLM(cfg_hf).eval()
    cfg = config_from_hf_llama(cfg_hf).replace(
        dtype=jnp.float32, param_dtype=jnp.float32, attn_impl="xla"
    )
    params = from_hf_llama(hf, cfg)
    hp = HybridParallelConfig(
        layer_strategies=[LayerStrategy(**hp_kwargs.pop("layer", {}))] * n_layers,
        mixed_precision="fp32",
        **hp_kwargs,
    )
    rt = build_runtime(
        cfg, hp, adam=AdamConfig(lr=1e-3), global_batch_size=8, seq_len=16
    )
    state = rt.init_state_from(params)
    tokens = np.random.RandomState(1).randint(0, 128, (8, 17))
    ours = float(rt.eval_loss(state, jnp.asarray(tokens, jnp.int32)))
    ref = hf_ce_loss(hf, tokens)
    assert abs(ours - ref) < atol, (ours, ref)
    # and it trains from those weights
    state, loss = rt.train_step(state, jnp.asarray(tokens, jnp.int32))
    assert np.isfinite(float(loss))


def test_hf_weights_runtime_gspmd():
    """pp=1 GSPMD path with tp+zero3: loss from imported weights matches HF."""
    runtime_loss_parity({"pp": 1, "layer": {"tp": 2, "dp_type": "zero3"}})


def test_hf_weights_runtime_pipeline():
    """pp=2 pipeline path: init_state_from restacks flat layers per stage."""
    runtime_loss_parity({"pp": 2, "chunks": 2, "pipeline_type": "gpipe"})


def test_hf_weights_runtime_interleaved():
    """pp=2 x vpp=2 interleaved: the (pp, vpp) round-robin restack."""
    runtime_loss_parity({"pp": 2, "vpp": 2, "chunks": 2, "pipeline_type": "gpipe"},
                        n_layers=4)


def test_cli_train_load_hf(tmp_path, capsys):
    """--load_hf: the trainer takes its model shape and weights from the HF
    checkpoint (the reference's train_dist.py builds from the HF model the
    same way)."""
    from galvatron_tpu.cli import main as cli_main

    hf = tiny_hf()
    hf.save_pretrained(tmp_path / "ckpt")
    rc = cli_main(
        ["train", "--load_hf", str(tmp_path / "ckpt"),
         "--global_train_batch_size", "8", "--train_iters", "3",
         "--global_tp_deg", "2", "--mixed_precision", "fp32",
         "--check_loss", "1", "--seq_length", "16"]
    )
    assert rc == 0
    out = capsys.readouterr().out
    assert "initialized from HF checkpoint" in out


def test_hf_weights_runtime_1f1b():
    """pp=2 pipedream_flush (1F1B) runtime also supports init_state_from."""
    runtime_loss_parity({"pp": 2, "chunks": 2, "pipeline_type": "pipedream_flush"})


def test_rejects_rope_scaling_and_biases():
    cfg = transformers.LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64, num_hidden_layers=1,
        num_attention_heads=2, rope_scaling={"rope_type": "linear", "factor": 2.0},
    )
    with pytest.raises(ValueError, match="rope_scaling"):
        config_from_hf_llama(cfg)
    cfg2 = transformers.LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=64, num_hidden_layers=1,
        num_attention_heads=2, attention_bias=True,
    )
    with pytest.raises(ValueError, match="bias"):
        config_from_hf_llama(cfg2)


def test_to_hf_llama_roundtrip():
    """Export: a fine-tuned param tree loads into HF LlamaForCausalLM and
    reproduces our logits — fine-tune here, serve on any HF stack."""
    from galvatron_tpu.models.convert import from_hf_llama, to_hf_llama

    for kv in (4, 2):  # blocked and GQA-interleaved unpacking
        hf = tiny_hf(num_kv_heads=kv)
        cfg = config_from_hf_llama(hf.config).replace(
            dtype=jnp.float32, param_dtype=jnp.float32, attn_impl="xla"
        )
        params = from_hf_llama(hf, cfg)
        # perturb so the export is not just the identity of the import
        params["layers"][0]["attn"]["wo"] = params["layers"][0]["attn"]["wo"] + 0.01
        sd = {k: torch.tensor(v) for k, v in to_hf_llama(params, cfg).items()}
        hf2 = tiny_hf(num_kv_heads=kv)
        hf2.load_state_dict(sd)
        tokens = np.random.RandomState(4).randint(0, cfg.vocab_size, (2, 12))
        with torch.no_grad():
            ref = hf2(torch.tensor(tokens)).logits.numpy()
        ours = np.asarray(forward(params, jnp.asarray(tokens, jnp.int32), cfg))
        np.testing.assert_allclose(ours, ref, rtol=2e-4, atol=2e-4)


def test_cli_export_hf(tmp_path, capsys):
    """train --save → export-hf → HF checkpoint loads back via load_hf."""
    from galvatron_tpu.cli import main as cli_main
    from galvatron_tpu.models.convert import load_hf_checkpoint

    save = str(tmp_path / "ckpt")
    args = ["--model_size", "llama-0.3b", "--hidden_size", "64", "--num_layers", "2",
            "--num_heads", "4", "--ffn_dim", "112", "--vocab_size", "128",
            "--seq_length", "16"]
    rc = cli_main(["train", *args, "--global_train_batch_size", "8",
                   "--train_iters", "2", "--mixed_precision", "fp32",
                   "--save", save])
    assert rc == 0
    out_dir = str(tmp_path / "hf")
    rc = cli_main(["export-hf", *args, "--load", save, "--output_dir", out_dir])
    assert rc == 0
    params, cfg = load_hf_checkpoint(out_dir)
    assert cfg.hidden_size == 64 and cfg.num_layers == 2


# ---------------------------------------------------------------------------
# Baichuan (trust_remote_code architecture: the torch reference forward is
# implemented here from the published modeling code's math — W_pack fused
# projection, RMSNorm/SwiGLU, rotary (7B) or ALiBi (13B) — because
# transformers ships no Baichuan class to instantiate)
# ---------------------------------------------------------------------------


def make_baichuan_sd(seed, vocab, h, n_layers, ffn):
    rng = np.random.RandomState(seed)
    t = lambda *shp: torch.from_numpy(
        (rng.standard_normal(shp) * 0.05).astype(np.float32)
    )
    ones = lambda: torch.from_numpy(
        (1.0 + 0.1 * rng.standard_normal(h)).astype(np.float32)
    )
    sd = {
        "model.embed_tokens.weight": t(vocab, h),
        "model.norm.weight": ones(),
        "lm_head.weight": t(vocab, h),
    }
    for i in range(n_layers):
        pre = f"model.layers.{i}."
        sd[pre + "self_attn.W_pack.weight"] = t(3 * h, h)
        sd[pre + "self_attn.o_proj.weight"] = t(h, h)
        sd[pre + "mlp.gate_proj.weight"] = t(ffn, h)
        sd[pre + "mlp.up_proj.weight"] = t(ffn, h)
        sd[pre + "mlp.down_proj.weight"] = t(h, ffn)
        sd[pre + "input_layernorm.weight"] = ones()
        sd[pre + "post_attention_layernorm.weight"] = ones()
    return sd


def torch_baichuan_forward(sd, tokens, n_heads, n_layers, alibi, eps=1e-6):
    """Reference forward per the published Baichuan-1 modeling code: fused
    W_pack [Q; K; V] rows, HF-llama rotate_half rotary (7B) or ALiBi slope
    bias (13B), RMSNorm, SwiGLU, untied head."""
    x = sd["model.embed_tokens.weight"][torch.tensor(tokens)]
    b, s, h = x.shape
    hd = h // n_heads

    def rms(v, w):
        return v * torch.rsqrt(v.pow(2).mean(-1, keepdim=True) + eps) * w

    if not alibi:
        inv = 1.0 / (10000.0 ** (torch.arange(0, hd, 2).float() / hd))
        fr = torch.outer(torch.arange(s).float(), inv)
        emb = torch.cat([fr, fr], dim=-1)
        cos, sin = emb.cos(), emb.sin()  # (s, hd)

        def rope(v):  # (b, n, s, hd), rotate_half convention
            v1, v2 = v[..., : hd // 2], v[..., hd // 2 :]
            rot = torch.cat([-v2, v1], dim=-1)
            return v * cos + rot * sin

    mask = torch.full((s, s), float("-inf")).triu(1)
    if alibi:
        slopes = torch.tensor(
            [2.0 ** (-8.0 * (i + 1) / n_heads) for i in range(n_heads)]
        )
        pos = torch.arange(s).float()
        rel = pos[None, :] - pos[:, None]  # j - i, negative below diagonal
        bias = slopes[:, None, None] * rel[None]  # (n, s, s)

    for i in range(n_layers):
        pre = f"model.layers.{i}."
        r = rms(x, sd[pre + "input_layernorm.weight"])
        qkv = r @ sd[pre + "self_attn.W_pack.weight"].T  # (b, s, 3h)
        q, k, v = qkv.split(h, dim=-1)
        shp = lambda t_: t_.view(b, s, n_heads, hd).transpose(1, 2)
        q, k, v = shp(q), shp(k), shp(v)
        if not alibi:
            q, k = rope(q), rope(k)
        scores = q @ k.transpose(-1, -2) / np.sqrt(hd)
        if alibi:
            scores = scores + bias[None]
        scores = scores + mask
        ctx = torch.softmax(scores, dim=-1) @ v  # (b, n, s, hd)
        ctx = ctx.transpose(1, 2).reshape(b, s, h)
        x = x + ctx @ sd[pre + "self_attn.o_proj.weight"].T
        r = rms(x, sd[pre + "post_attention_layernorm.weight"])
        g = r @ sd[pre + "mlp.gate_proj.weight"].T
        u = r @ sd[pre + "mlp.up_proj.weight"].T
        x = x + (torch.nn.functional.silu(g) * u) @ sd[pre + "mlp.down_proj.weight"].T
    x = rms(x, sd["model.norm.weight"])
    return (x @ sd["lm_head.weight"].T).numpy()


def baichuan_parity(alibi: bool, seed: int):
    from types import SimpleNamespace

    from galvatron_tpu.models.convert import (
        config_from_hf_baichuan,
        from_hf_baichuan,
    )

    ns = dict(
        vocab_size=128, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=112, rms_norm_eps=1e-6,
        tie_word_embeddings=False,
    )
    if alibi:
        ns["model_max_length"] = 64  # 13B-style config field
        hf_cfg = SimpleNamespace(**ns)
    else:
        ns["max_position_embeddings"] = 64  # 7B-style
        hf_cfg = SimpleNamespace(**ns)
    cfg = config_from_hf_baichuan(hf_cfg).replace(
        dtype=jnp.float32, param_dtype=jnp.float32, attn_impl="xla"
    )
    assert cfg.pos_embed == ("alibi" if alibi else "rope")
    sd = make_baichuan_sd(seed, 128, 64, 2, 112)
    params = from_hf_baichuan(sd, cfg)
    tokens = np.random.RandomState(seed).randint(0, 128, (2, 16))
    with torch.no_grad():
        ref = torch_baichuan_forward(sd, tokens, 4, 2, alibi)
    ours = np.asarray(forward(params, jnp.asarray(tokens, jnp.int32), cfg))
    np.testing.assert_allclose(ours, ref, rtol=2e-4, atol=2e-4)


def test_hf_baichuan7b_logit_parity_rotary():
    baichuan_parity(alibi=False, seed=7)


def test_hf_baichuan13b_logit_parity_alibi():
    """13B-style ALiBi path: the relative-position slope bias must match the
    published absolute-position form (softmax-shift-invariant)."""
    baichuan_parity(alibi=True, seed=13)


def test_load_hf_baichuan_through_runtime(tmp_path):
    """Baichuan checkpoint dir (config.json + torch .bin, 13B-style ALiBi) →
    load_hf_checkpoint (raw state-dict path, no remote code executed) →
    hybrid runtime trains."""
    import json

    from galvatron_tpu.core.optim import AdamConfig
    from galvatron_tpu.core.strategy import HybridParallelConfig
    from galvatron_tpu.models.convert import load_hf_checkpoint
    from galvatron_tpu.parallel.hybrid import build_runtime

    d = tmp_path / "baichuan"
    d.mkdir()
    sd = make_baichuan_sd(5, 128, 64, 2, 112)
    torch.save(sd, d / "pytorch_model.bin")
    (d / "config.json").write_text(json.dumps({
        "model_type": "baichuan", "vocab_size": 128, "hidden_size": 64,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "intermediate_size": 112, "rms_norm_eps": 1e-6,
        "model_max_length": 64, "tie_word_embeddings": False,
    }))
    params, cfg = load_hf_checkpoint(str(d))
    assert cfg.pos_embed == "alibi" and cfg.max_seq_len == 64
    cfg = cfg.replace(dtype=jnp.float32, param_dtype=jnp.float32, attn_impl="xla")
    hp = HybridParallelConfig.uniform(2, tp=2, vocab_tp=2, mixed_precision="fp32")
    rt = build_runtime(cfg, hp, adam=AdamConfig(lr=1e-3), global_batch_size=8, seq_len=16)
    state = rt.init_state_from(params)
    batch = jnp.asarray(np.random.RandomState(5).randint(0, 128, (8, 17)), jnp.int32)
    l0 = float(rt.eval_loss(state, batch))
    with torch.no_grad():
        logits = torch.from_numpy(
            torch_baichuan_forward(sd, np.asarray(batch[:, :-1]), 4, 2, alibi=True)
        )
    ref = float(torch.nn.functional.cross_entropy(
        logits.reshape(-1, 128),
        torch.tensor(np.asarray(batch[:, 1:])).reshape(-1).long(),
    ))
    assert abs(l0 - ref) < 2e-4, (l0, ref)
    state, l1 = rt.train_step(state, batch)
    state, l2 = rt.train_step(state, batch)
    assert np.isfinite(float(l2)) and float(l2) < float(l1)


def test_load_hf_baichuan_sharded_safetensors_rotary(tmp_path):
    """Disk-path coverage the single-.bin test misses: a SHARDED safetensors
    checkpoint (index.json + two shards) with a 7B-style ROTARY config —
    loads through load_hf_checkpoint and matches the torch reference."""
    import json

    from safetensors.numpy import save_file

    from galvatron_tpu.models.convert import load_hf_checkpoint

    d = tmp_path / "bc7b"
    d.mkdir()
    sd = make_baichuan_sd(9, 128, 64, 2, 112)
    names = sorted(sd)
    half = len(names) // 2
    shards = {
        "model-00001-of-00002.safetensors": names[:half],
        "model-00002-of-00002.safetensors": names[half:],
    }
    weight_map = {}
    for fn, keys in shards.items():
        save_file({k: sd[k].numpy() for k in keys}, str(d / fn))
        weight_map.update({k: fn for k in keys})
    (d / "model.safetensors.index.json").write_text(
        json.dumps({"weight_map": weight_map})
    )
    (d / "config.json").write_text(json.dumps({
        "model_type": "baichuan", "vocab_size": 128, "hidden_size": 64,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "intermediate_size": 112, "rms_norm_eps": 1e-6,
        "max_position_embeddings": 64, "tie_word_embeddings": False,
    }))
    params, cfg = load_hf_checkpoint(str(d))
    assert cfg.pos_embed == "rope"
    cfg = cfg.replace(dtype=jnp.float32, param_dtype=jnp.float32, attn_impl="xla")
    tokens = np.random.RandomState(9).randint(0, 128, (2, 16))
    with torch.no_grad():
        ref = torch_baichuan_forward(sd, tokens, 4, 2, alibi=False)
    ours = np.asarray(forward(params, jnp.asarray(tokens, jnp.int32), cfg))
    np.testing.assert_allclose(ours, ref, rtol=2e-4, atol=2e-4)


def test_baichuan2_rejected():
    """Baichuan-2 shares model_type 'baichuan' but needs NormHead math this
    importer lacks — its 125696-token vocab must be a hard error, not a
    silent garbage import."""
    from types import SimpleNamespace

    from galvatron_tpu.models.convert import config_from_hf_baichuan

    with pytest.raises(ValueError, match="Baichuan-2"):
        config_from_hf_baichuan(SimpleNamespace(
            vocab_size=125696, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, intermediate_size=112,
            model_max_length=64,
        ))
