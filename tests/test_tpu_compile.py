"""Compile the served path's kernels and decode step for a described TPU
v5e, with no chip attached.

The TPU compiler is installed next to JAX and compiles for a topology
that is only described, so these tests catch what interpret mode cannot:
block shapes Mosaic refuses, ops it cannot lower, and programs that do
not fit the chip's 16 GB of HBM.  Nothing runs; a compile that passes is
not a chip run.

The topology is described inside a module fixture (never at import):
only one process at a time may load the TPU library, and pytest workers
that each imported it would fail.  The persistent compilation cache is
off around these compiles, since what it would write cannot be read back
without a chip.
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels import decode_attention as dec_mod
from repro.kernels import flash_attention as fa_mod
from repro.kernels import rwkv6_scan as rwkv_mod
from repro.kernels import ssd_scan as ssd_mod
from repro.models import model_zoo

HBM_BYTES = 16e9            # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", before)


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _compile(fn, *args, **static):
    compiled = jax.jit(lambda *a: fn(*a, **static)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("Hq,Hkv,D", [
    (32, 32, 64),           # stablelm-1.6b
    (10, 2, 128),           # qwen2.5-14b, per chip at TP=4
])
def test_decode_attention_compiles(spec, Hq, Hkv, D):
    B, T = 16, 2048
    _compile(dec_mod.decode_attention, spec((B, Hq, D)),
             spec((B, T, Hkv, D)), spec((B, T, Hkv, D)),
             spec((B,), jnp.int32), spec((B, T), jnp.int32))


def test_paged_decode_attention_compiles(spec):
    B, T, H, D, page = 16, 2048, 32, 64, 16
    ppr = T // page
    P = B * ppr + 1                          # pool plus the null page
    _compile(dec_mod.paged_decode_attention, spec((B, H, D)),
             spec((P, page, H, D)), spec((P, page, H, D)),
             spec((B, ppr), jnp.int32), spec((B,), jnp.int32),
             spec((P, page), jnp.int32))


def test_flash_attention_prefill_compiles(spec):
    B, S, H, D = 1, 512, 32, 64              # stablelm, 512-token prompt
    _compile(fa_mod.flash_attention, spec((B, S, H, D)), spec((B, S, H, D)),
             spec((B, S, H, D)), spec((B, S), jnp.int32),
             spec((B, S), jnp.int32))


def test_rwkv6_scan_compiles(spec):
    B, S, H, D = 1, 256, 64, 64              # rwkv6-7b heads
    _compile(rwkv_mod.rwkv6_scan, *(spec((B, S, H, D)) for _ in range(4)),
             spec((H, D), jnp.float32), spec((B, H, D, D), jnp.float32),
             chunk=128)


def test_ssd_scan_compiles(spec):
    B, S, I, N = 1, 256, 3200, 16            # hymba-1.5b mamba heads
    _compile(ssd_mod.ssd_scan, spec((B, S, I, N), jnp.float32),
             spec((B, S, I, N), jnp.float32), spec((B, I, N), jnp.float32))


def test_stablelm_decode_step_fits_one_chip(spec):
    """The full-width decode step the one-chip smoke run serves: 16 slots
    x 2048 tokens of bf16 cache, masked by the active rows and donated as
    the engine does.  Its arguments, output and temporaries fit 16 GB
    (the cache is updated in place: no second copy of it)."""
    cfg = configs.get_config("stablelm-1.6b")
    B, T = 16, 2048
    params = jax.tree.map(lambda s: spec(s.shape, s.dtype),
                          model_zoo.abstract_params(cfg))
    cache = jax.tree.map(lambda s: spec(s.shape, s.dtype),
                         model_zoo.init_cache(cfg, B, T, abstract=True))

    def step(params, cache, tokens, t, active):
        return model_zoo.decode(cfg, params, cache, tokens, t, active=active)

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, spec((B,), jnp.int32), spec((B,), jnp.int32),
        spec((B,), jnp.bool_)).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, mem


def test_stablelm_pallas_decode_step_compiles(spec, monkeypatch):
    """The same step with ``use_pallas=True``: the decode kernel inside
    the scanned layer stack of the real model.  The process's backend is
    the CPU, so the test steers the kernel wrappers to Mosaic itself."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = dataclasses.replace(configs.get_config("stablelm-1.6b"),
                              use_pallas=True, num_layers=2)
    B, T = 16, 2048
    params = jax.tree.map(lambda s: spec(s.shape, s.dtype),
                          model_zoo.abstract_params(cfg))
    cache = jax.tree.map(lambda s: spec(s.shape, s.dtype),
                         model_zoo.init_cache(cfg, B, T, abstract=True))
    _compile(lambda p, c, tok, t: model_zoo.decode(cfg, p, c, tok, t),
             params, cache, spec((B,), jnp.int32), spec((B,), jnp.int32))


# -- the decode's cache update: one token per row, in place ---------------

#: one instruction of HLO text: name, result type, opcode, operands
_INSTR = re.compile(r"^\s*(ROOT )?%?(\S+) = (\(.*?\)|\w+\[[\d,]*\]\S*) "
                    r"([\w-]+)\((.*)$")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?(\S+) .*\{$")


def _elements(typ: str) -> int:
    """Largest element count among the arrays of a result type."""
    best = 0
    for dims in re.findall(r"\w+\[([\d,]*)\]", typ):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        best = max(best, n)
    return best


def materialized(hlo: str):
    """``(name, kind, elements)`` of every array the program writes: each
    instruction outside a fused computation, a fusion named by the root
    of what it fuses (past bitcasts)."""
    comps, order, cur = {}, [], None
    for line in hlo.splitlines():
        m = _COMPUTATION.match(line)
        if m and not line.startswith(" "):
            cur = comps.setdefault(m.group(1), {})
            order.append(m.group(1))
            continue
        m = _INSTR.match(line)
        if m and cur is not None:
            operands = re.findall(r"%([\w.\-]+)", m.group(5).split("),")[0])
            cur[m.group(2)] = (m.group(3), m.group(4), operands,
                               bool(m.group(1)), m.group(5))
    fused = {c for comp in comps.values() for *_, rest in comp.values()
             for c in re.findall(r"calls=%([\w.\-]+)", rest)}

    def root_kind(name: str) -> str:
        body = comps[name]
        _, op, operands, _, _ = next(v for v in body.values() if v[3])
        while op == "bitcast" and operands and operands[0] in body:
            _, op, operands, _, _ = body[operands[0]]
        return op

    out = []
    for cname in order:
        if cname in fused:
            continue
        for name, (typ, op, _, _, rest) in comps[cname].items():
            if op in ("parameter", "get-tuple-element", "bitcast", "tuple",
                      "while", "call", "conditional", "constant"):
                continue
            if op == "fusion":
                callee = re.search(r"calls=%([\w.\-]+)", rest).group(1)
                op = root_kind(callee)
            out.append((name, op, _elements(typ)))
    return out


@pytest.mark.parametrize("arch,layers", [
    ("stablelm-1.6b", None),       # head_dim 64: two heads a 128-lane row
    ("qwen2.5-14b", 12),           # head_dim 128, GQA: the one-chip stage
])
def test_decode_writes_only_the_new_token(spec, arch, layers):
    """The served decode step (16 rows x 2048 positions, donated cache):
    no copy, select or dynamic-update-slice writes an array the size of
    one layer's K or V or more, so no layer of the stacked cache is
    copied, relaid out, masked or written back; the only writes of the
    stack's size are its in-place scatters; and the temporaries stay
    below one layer's K and V."""
    cfg = configs.get_config(arch)
    if layers:
        cfg = dataclasses.replace(cfg, num_layers=layers)
    B, T = 16, 2048
    params = jax.tree.map(lambda s: spec(s.shape, s.dtype),
                          model_zoo.abstract_params(cfg))
    cache = jax.tree.map(lambda s: spec(s.shape, s.dtype),
                         model_zoo.init_cache(cfg, B, T, abstract=True))

    def step(params, cache, tokens, t, active):
        return model_zoo.decode(cfg, params, cache, tokens, t, active=active)

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, spec((B,), jnp.int32), spec((B,), jnp.int32),
        spec((B,), jnp.bool_)).compile()
    layer = B * T * cfg.num_kv_heads * cfg.head_dim         # one K (or V)
    written = [w for w in materialized(compiled.as_text())
               if w[2] >= layer]
    assert not [w for w in written
                if w[1] in ("copy", "copy-start", "select",
                            "dynamic-update-slice")], written
    stack = cfg.num_layers * layer
    assert sorted(w[1] for w in written if w[2] >= stack) == [
        "scatter", "scatter"], written
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 2 * layer * 2, temp                       # bf16 K + V
