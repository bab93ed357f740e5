"""The lane-dense KV cache and the decode's token-only write.

``transformer.init_cache`` stores K and V as ``(L, B, W, Hkv/f, f*Dh)``
with ``f = kv_fold(cfg)`` heads side by side in each row, and a scanned
decode writes only each active row's new token into the donated stack.
These tests hold that path to the math it replaced, on the CPU, for f = 1
(head_dim 128) and f = 2 (head_dim 64), with grouped and ungrouped
queries and with a rolling sliding-window cache: every decode step's
logits against a full-sequence forward, inactive rows bit-for-bit, and
the paged pool and a two-device tensor-parallel tier token for token
against the dense endpoint.
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import model_zoo, transformer
from repro.models.common import ModelConfig
from repro.serving import sharded
from repro.serving.engine import Endpoint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KEY = jax.random.PRNGKey(0)

#: (head_dim, num_heads, num_kv_heads): f = 1 and f = 2, each with one
#: query per kv head (the multiply-reduce) and with grouped queries (the dot)
SHAPES = [(128, 4, 4), (128, 4, 2), (64, 4, 4), (64, 8, 4)]


def small_config(head_dim: int, num_heads: int, num_kv_heads: int,
                 window=None) -> ModelConfig:
    return ModelConfig(
        name=f"fold-{head_dim}-{num_heads}-{num_kv_heads}", family="dense",
        num_layers=2, d_model=128, num_heads=num_heads,
        num_kv_heads=num_kv_heads, head_dim=head_dim, d_ff=256,
        vocab_size=256, qkv_bias=True, sliding_window=window,
        param_dtype=jnp.float32, compute_dtype=jnp.float32,
        attn_chunk=16, q_chunk=32)


def full_logits(cfg, params, tokens):
    """Every position's logits from one full-sequence forward (no cache)."""
    emb, pos = transformer.assemble_embeds(cfg, params, {"tokens": tokens})
    x, _, _ = transformer.forward(cfg, params, emb, pos, None, "train")
    return transformer.output_head(cfg, params, x)


def test_fold_follows_head_dim_and_kv_heads():
    assert transformer.kv_fold(configs.get_config("stablelm-1.6b")) == 2
    assert transformer.kv_fold(configs.get_config("qwen2.5-14b")) == 1
    assert transformer.kv_fold(small_config(32, 8, 4)) == 4
    assert transformer.kv_fold(small_config(64, 3, 3)) == 1   # 3 % 2
    assert transformer.kv_fold(small_config(48, 4, 4)) == 1   # 128 % 48
    assert transformer.kv_fold(small_config(256, 4, 4)) == 1
    # families with their own cache keep one head to a row
    for arch in ("hymba-1.5b", "rwkv6-7b"):
        assert model_zoo.kv_fold(configs.get_config(arch)) == 1
    assert model_zoo.kv_fold(configs.get_config("stablelm-1.6b")) == 2


@pytest.mark.parametrize("D,Hq,Hkv", SHAPES)
def test_cache_is_the_same_bytes_folded(D, Hq, Hkv):
    cfg = small_config(D, Hq, Hkv)
    f = transformer.kv_fold(cfg)
    cache = model_zoo.init_cache(cfg, 3, 16, abstract=True)
    assert cache["k"].shape == (2, 3, 16, Hkv // f, f * D)
    assert cache["pos"].shape == (2, 3, 16)
    # prefill writes head h of each token at its row-major place
    params = model_zoo.init(KEY, cfg)
    toks = jax.random.randint(KEY, (3, 5), 0, cfg.vocab_size)
    _, c = model_zoo.prefill(cfg, params, {"tokens": toks},
                             model_zoo.init_cache(cfg, 3, 16))
    unfolded = np.asarray(c["k"]).reshape(2, 3, 16, Hkv, D)
    emb, pos = transformer.assemble_embeds(cfg, params, {"tokens": toks})
    p0 = {k[len("layers/"):]: v[0] for k, v in params.items()
          if k.startswith("layers/")}
    h = transformer.apply_norm(cfg, p0, "attn/norm", emb)
    _, k0, _ = transformer.qkv_project(cfg, p0, h, pos)
    np.testing.assert_allclose(unfolded[0, :, :5], np.asarray(k0),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("window", [None, 8])
@pytest.mark.parametrize("D,Hq,Hkv", SHAPES)
def test_decode_logits_match_full_sequence(D, Hq, Hkv, window):
    """Prefill 6 tokens, then decode 10 more, one at a time: each step's
    logits match a full-sequence forward at that position (the rolling
    8-slot cache wraps twice)."""
    cfg = small_config(D, Hq, Hkv, window)
    params = model_zoo.init(jax.random.fold_in(KEY, D + Hq + Hkv), cfg)
    B, S, n = 2, 6, 10
    toks = jax.random.randint(KEY, (B, S + n), 0, cfg.vocab_size)
    want = np.asarray(full_logits(cfg, params, toks), np.float32)
    cache = model_zoo.init_cache(cfg, B, S + n)
    prefill = jax.jit(lambda p, t, c: model_zoo.prefill(cfg, p, {"tokens": t},
                                                        c))
    decode = jax.jit(lambda p, c, t, pos: model_zoo.decode(cfg, p, c, t, pos),
                     donate_argnums=(1,))
    lg, cache = prefill(params, toks[:, :S], cache)
    got = [np.asarray(lg, np.float32)]
    for i in range(S, S + n - 1):
        lg, cache = decode(params, cache, toks[:, i],
                           jnp.full((B,), i, jnp.int32))
        got.append(np.asarray(lg, np.float32))
    for step, (a, b) in enumerate(zip(got, want[:, S - 1:].swapaxes(0, 1))):
        rel = np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-9)
        assert rel < 2e-3, (step, rel)


@pytest.mark.parametrize("window", [None, 8])
@pytest.mark.parametrize("D,Hq,Hkv", SHAPES)
def test_inactive_rows_keep_their_cache_bit_for_bit(D, Hq, Hkv, window):
    cfg = small_config(D, Hq, Hkv, window)
    params = model_zoo.init(KEY, cfg)
    B, S = 3, 5
    toks = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    _, cache = model_zoo.prefill(cfg, params, {"tokens": toks},
                                 model_zoo.init_cache(cfg, B, 16))
    decode = jax.jit(lambda p, c, t, pos, a: model_zoo.decode(
        cfg, p, c, t, pos, active=a))
    active = jnp.array([True, False, True])
    before = jax.tree.map(np.asarray, cache)
    for i in range(S, S + 12):
        cache_next = decode(params, cache, toks[:, i % S],
                            jnp.full((B,), i, jnp.int32), active)[1]
        now = jax.tree.map(np.asarray, cache_next)
        for name in ("k", "v", "pos"):
            np.testing.assert_array_equal(now[name][:, 1],
                                          before[name][:, 1])
            # an active row writes exactly its new token's slot
            W = now[name].shape[2]
            diff = np.any(now[name][:, 0] != np.asarray(cache[name])[:, 0],
                          axis=tuple(range(2, now[name].ndim - 1)))
            assert set(np.nonzero(diff.reshape(2, W))[1]) <= {i % W}
        assert np.all(now["pos"][:, 0, i % W] == i)
        cache = cache_next


def _serve(cfg, params, **kw):
    """Three requests through one Endpoint; their token streams."""
    ep = Endpoint(cfg, params, slots=4, max_len=32, **kw)
    rng = np.random.RandomState(7)
    prompts = {s: rng.randint(0, cfg.vocab_size,
                              size=(5 + 3 * s,)).astype(np.int32)
               for s in range(3)}
    for p in prompts.values():
        ep.try_claim(p, max_new=8)
    first = ep.prefill_batch(prompts)
    streams = {s: [int(v)] for s, v in first.items()}
    cur = dict(first)
    for step in range(7):
        if step == 3:                      # slot 1 retires mid-stream
            cur.pop(1)
        cur = ep.decode_all(cur)
        for s, v in cur.items():
            streams[s].append(int(v))
    return streams, ep


@pytest.mark.parametrize("D,Hq,Hkv", SHAPES)
def test_paged_endpoint_matches_dense(D, Hq, Hkv):
    cfg = small_config(D, Hq, Hkv)
    params = model_zoo.init(KEY, cfg)
    dense, ep = _serve(cfg, params)
    paged, pep = _serve(cfg, params, paged=True, page_size=8)
    assert ep.kv_fold == pep.kv_fold == transformer.kv_fold(cfg)
    assert paged == dense


def test_tp_needs_the_folded_kv_heads_to_split():
    with pytest.raises(ValueError, match="kv_fold"):
        sharded.validate_tp(small_config(64, 4, 2), 2)      # 1 folded head
    sharded.validate_tp(small_config(64, 8, 4), 2)          # 2 folded heads


_TP_CODE = textwrap.dedent("""\
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import json, sys
    import jax
    sys.path.insert(0, os.path.join(sys.argv[1], "tests"))
    from test_kv_fold import SHAPES, _serve, small_config
    from repro.launch import mesh as mesh_mod
    from repro.models import model_zoo

    mesh = mesh_mod.make_mesh((1, 2), ("data", "model"))
    out = {}
    for D, Hq, Hkv in SHAPES:
        cfg = small_config(D, Hq, Hkv)
        params = model_zoo.init(jax.random.PRNGKey(0), cfg)
        dense, _ = _serve(cfg, params)
        tp, _ = _serve(cfg, params, mesh=mesh)
        out[f"{D},{Hq},{Hkv}"] = [len(jax.devices()), dense == tp]
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def tp_streams():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _TP_CODE, REPO], env=env,
                         capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("D,Hq,Hkv", SHAPES)
def test_two_device_tier_matches_dense(tp_streams, D, Hq, Hkv):
    ndev, same = tp_streams[f"{D},{Hq},{Hkv}"]
    assert ndev == 2
    assert same, "tensor-parallel token stream diverged from dense"
