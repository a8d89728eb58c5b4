"""Compiles for a described TPU v5e, with no chip attached: the Pallas
kernels at real widths must lower to Mosaic (``tpu_custom_call`` in the
HLO), the llama3.2-3b serving steps at published widths must fit one
chip's 16 GiB, and a decode step must update its donated KV cache (or
latent cache) in place, with no copy of the cache or of a layer of it.
Nothing runs, so these say nothing about results or time.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and the test workers all import this
file.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", before)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def chip_plan(topo):
    from repro.core.plan import ShardingPlan
    devs = np.array(topo.devices[:1]).reshape(1, 1)
    return ShardingPlan(mesh=Mesh(devs, axis_names=("data", "model")))


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _a2a(lg, x):
    from repro.kernels.a2a_fused import a2a_fused

    def expert(j):
        return lambda y: y * (j + 2.0) - j
    return a2a_fused(lg, x, [expert(j) for j in range(8)], 4096,
                     interpret=False)


def _router(lg):
    from repro.kernels.router_topk import router_topk
    return router_topk(lg, 2, 1280, block_t=256, interpret=False)


def _ssd(q, k, v, la):
    from repro.kernels.ssd_scan import ssd_scan
    return ssd_scan(q, k, v, la, chunk=128, interpret=False)


def _grouped(x, w, sizes):
    from repro.kernels.grouped_matmul import grouped_matmul
    return grouped_matmul(x, w, sizes, interpret=False)


def _flash(q, k, v):
    from repro.kernels.flash_attention import flash_attention
    return flash_attention(q, k, v, interpret=False)


# (kernel, argument shapes and dtypes): a2a at the stream graph's widths,
# the router at a Mixtral-like 8 experts top-2, the scan at Mamba2-like
# head widths, attention at llama3.2-3b's 24/8 heads of 128, the grouped
# matmul at a Kimi-K2 decode step's held experts (64 slots x top-8 rows)
KERNELS = {
    "a2a_fused": (_a2a, [((4096, 8), jnp.float32),
                         ((4096, 1024), jnp.float32)]),
    "router_topk": (_router, [((4096, 8), jnp.float32)]),
    "ssd_scan": (_ssd, [((2, 8, 2048, 128), jnp.bfloat16),
                        ((2, 8, 2048, 128), jnp.bfloat16),
                        ((2, 8, 2048, 64), jnp.bfloat16),
                        ((2, 8, 2048), jnp.float32)]),
    "grouped_matmul": (_grouped, [((512, 7168), jnp.bfloat16),
                                  ((8, 7168, 2048), jnp.bfloat16),
                                  ((8,), jnp.int32)]),
    "flash_attention": (_flash, [((1, 24, 2048, 128), jnp.bfloat16),
                                 ((1, 8, 2048, 128), jnp.bfloat16),
                                 ((1, 8, 2048, 128), jnp.bfloat16)]),
}


@pytest.mark.kernels
@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_to_mosaic_for_v5e(name, one_chip):
    fn, shapes = KERNELS[name]
    args = [_sds(s, d, one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _fits(compiled):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert total < HBM_BYTES, (total, m)
    return m


@pytest.fixture(scope="module")
def llama(chip_plan):
    from repro.configs import get
    from repro.models.lm import LM
    from repro.models.params import shape_structs
    cfg = get("llama3.2-3b")
    return cfg, shape_structs(LM(cfg).param_defs(), chip_plan)


def test_llama_prefill_fits_one_v5e(llama, chip_plan, one_chip):
    from repro.runtime.steps import make_prefill_step
    cfg, params = llama
    step = jax.jit(make_prefill_step(cfg, chip_plan, 1024))
    compiled = step.lower(
        params, {"tokens": _sds((1, 128), jnp.int32, one_chip)}).compile()
    m = _fits(compiled)
    assert m.argument_size_in_bytes > 7 * 10 ** 9      # all 3.6B bf16 params


def _decode(cfg, params, chip_plan, one_chip, B, cache_len, active=False,
            lower_only=False):
    """The serving engine's decode step (per-slot positions, cache donated,
    with ``active`` the live-slot mask the engine sends a held-expert
    model) compiled for one chip; returns (compiled, cache structs), or
    with ``lower_only`` (lowered, cache structs)."""
    from repro.configs import cache_specs
    from repro.runtime.steps import make_decode_step
    step = jax.jit(make_decode_step(cfg, chip_plan, cache_len),
                   donate_argnums=1)
    caches = cache_specs(cfg, B, cache_len, chip_plan)
    batch = {"token": _sds((B, 1), jnp.int32, one_chip),
             "pos": _sds((B,), jnp.int32, one_chip)}
    if active:
        batch["active"] = _sds((B,), jnp.int32, one_chip)
    lowered = step.lower(params, caches, batch)
    return (lowered if lower_only else lowered.compile()), caches


_ARRAY = re.compile(r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* (\S+?)\((.*)")
_OPERAND = re.compile(r"%([\w.\-]+)")


def _cache_sized(hlo, caches):
    """Instructions that materialize the cache or one layer of it: any
    ``copy`` with a stack's or a layer's element count, and any other
    instruction outside a fusion with a layer's.  Views (bitcasts) and the
    in-place writes (results of a stack's size computed from an operand of
    that size: fusions, scatters, the loop) are not listed."""
    stacks = {math.prod(c.shape) for c in jax.tree.leaves(caches)}
    layers = {math.prod(c.shape[1:]) for c in jax.tree.leaves(caches)}
    fused = set(re.findall(r" fusion\(.*?calls=%([\w.\-]+)", hlo))
    found, comp, sizes = [], None, {}
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) ", line)
        if head:
            comp = head.group(1)
            continue
        m = _ARRAY.match(line)
        if not m:
            continue
        name, dims, op, args = m.groups()
        n = math.prod(int(d) for d in dims.split(",")) if dims else 1
        sizes[name] = n
        in_place = n in stacks and any(
            sizes.get(a) == n for a in _OPERAND.findall(args.split(")")[0]))
        if op == "copy" and n in stacks | layers:
            found.append(name)
        elif (comp not in fused and n in layers and not in_place
              and op not in ("parameter", "bitcast")):
            found.append(name)
    return found


def test_llama_decode_fits_one_v5e_with_cache_donated(llama, chip_plan,
                                                      one_chip):
    cfg, params = llama
    compiled, caches = _decode(cfg, params, chip_plan, one_chip, 8, 1024)
    m = _fits(compiled)
    cache_bytes = sum(c.size * c.dtype.itemsize
                      for c in jax.tree.leaves(caches))
    assert m.alias_size_in_bytes >= cache_bytes   # updated in place
    assert m.temp_size_in_bytes < cache_bytes / cfg.n_layers, m


@pytest.fixture(scope="module")
def yi_stage(chip_plan):
    """Yi-34B at published widths, 8 of its 60 layers: the benchmark's
    serving configuration."""
    import dataclasses
    from repro.configs import get
    from repro.models.lm import LM
    from repro.models.params import shape_structs
    cfg = dataclasses.replace(get("yi-34b"), n_layers=8)
    return cfg, shape_structs(LM(cfg).param_defs(), chip_plan)


def test_yi_stage_decode_writes_cache_rows_in_place(yi_stage, chip_plan,
                                                    one_chip):
    """16 slots of 2,560: the step writes its new K/V rows into the donated
    1.34 GB cache and reads each layer where it lies, so its scratch stays
    below one layer's K+V, no copy of the cache or of a layer is made, and
    the layer scan is the only loop."""
    cfg, params = yi_stage
    compiled, caches = _decode(cfg, params, chip_plan, one_chip, 16, 2560)
    m = _fits(compiled)
    cache_bytes = sum(c.size * c.dtype.itemsize
                      for c in jax.tree.leaves(caches))
    assert m.alias_size_in_bytes >= cache_bytes
    assert m.temp_size_in_bytes < cache_bytes / cfg.n_layers, m
    hlo = compiled.as_text()
    assert _cache_sized(hlo, caches) == []
    scans = sum(kind != "shared_attn" for kind, _ in cfg.segments)
    assert len(re.findall(r" while\(", hlo)) == scans


# sha256 of the lowered StableHLO text of the dense cell's programs (Yi-34B,
# 8 layers) from the tree before the held expert layer computed only its
# routed rows: a change to the held layer must not move them
YI_TEXT = {
    "decode": ("d47bfc5acb625419df2b635ad3e33f38"
               "98cd1d07ee30ffda430021bc03b757a8"),
    "prefill": ("20d2d24bcd6d1fbdded1bc9f72127172"
                "7ac9abe1a8a4ed64108f6ecd07529e56"),
}


def test_yi_stage_programs_lower_to_the_same_text(yi_stage, chip_plan,
                                                  one_chip):
    import hashlib
    from repro.runtime.steps import make_prefill_step
    cfg, params = yi_stage
    decode, _ = _decode(cfg, params, chip_plan, one_chip, 16, 2560,
                        lower_only=True)
    prefill = jax.jit(make_prefill_step(cfg, chip_plan, 2560)).lower(
        params, {"tokens": _sds((1, 512), jnp.int32, one_chip)})
    got = {name: hashlib.sha256(low.as_text().encode()).hexdigest()
           for name, low in (("decode", decode), ("prefill", prefill))}
    assert got == YI_TEXT


@pytest.fixture
def on_chip(monkeypatch):
    """The program's Pallas kernels resolved as on the chip (Mosaic, not
    the interpreter), for a compile made here, where JAX's backend is the
    CPU."""
    from repro.kernels import backend
    monkeypatch.setattr(backend, "on_tpu", lambda: True)


def _op_name(line):
    m = re.search(r'op_name="([^"]*)"', line)
    return m.group(1) if m else ""


@pytest.fixture(scope="module")
def kimi_share(chip_plan):
    """Kimi-K2 at published widths as the benchmark cuts it: the dense
    layer and 8 MoE layers, experts 0-7 of 384, a vocabulary slice of
    20,480 rows."""
    import dataclasses
    from repro.configs import get
    from repro.models.lm import LM
    from repro.models.params import shape_structs
    cfg = dataclasses.replace(get("kimi-k2-1t-a32b"), n_layers=9,
                              vocab=20480, n_experts_held=8, expert_offset=0)
    return cfg, shape_structs(LM(cfg).param_defs(), chip_plan)


def test_kimi_share_decode_writes_latent_rows_in_place(kimi_share, chip_plan,
                                                       one_chip, on_chip):
    """64 slots of 3,584: the 9.6 GB of weights and the 2.6 GB latent cache
    fit one chip, the step writes its new latent rows into the donated
    cache and reads each layer where it lies, so its scratch stays below
    one layer of the cache, no copy of the cache or of a layer is made,
    and the MoE layer scan is the only loop (the one dense layer's is
    unrolled) but for the grouped matmul's group bookkeeping inside the
    expert layer (megablox's ``searchsorted``)."""
    cfg, params = kimi_share
    compiled, caches = _decode(cfg, params, chip_plan, one_chip, 64, 3584,
                               active=True)
    m = _fits(compiled)
    assert m.argument_size_in_bytes > 12 * 10 ** 9
    cache_bytes = sum(c.size * c.dtype.itemsize
                      for c in jax.tree.leaves(caches))
    assert m.alias_size_in_bytes >= cache_bytes
    assert m.temp_size_in_bytes < cache_bytes / cfg.n_layers, m
    hlo = compiled.as_text()
    assert _cache_sized(hlo, caches) == []
    loops = [_op_name(line) for line in hlo.splitlines()
             if " while(" in line]
    assert [n for n in loops if "/moe/" not in n] == [
        "jit(decode_step)/while"], loops
    assert all("/moe/jit(gmm)/" in n for n in loops if "/moe/" in n), loops


def test_kimi_share_decode_runs_held_experts_as_grouped_matmuls(
        kimi_share, chip_plan, one_chip, on_chip):
    """The held experts' three matrices run as three grouped-matmul
    kernels (``kernels/grouped_matmul.py``) in the expert layers' scan,
    each under ``jax.named_scope("moe")``, and no XLA conditional sits
    under "moe": its own event and its inner operations would both be
    counted as the scope's time by the benchmark's scope reader."""
    cfg, params = kimi_share
    compiled, _ = _decode(cfg, params, chip_plan, one_chip, 64, 3584,
                          active=True)
    lines = compiled.as_text().splitlines()
    grouped = [_op_name(line) for line in lines
               if "custom-call(" in line and "tpu_custom_call" in line]
    assert len(grouped) == 3, grouped
    assert all("/moe/" in n and "gmm" in n for n in grouped), grouped
    assert not [line for line in lines
                if " conditional(" in line and "/moe/" in _op_name(line)]


def test_kimi_share_longest_prefill_fits_one_v5e(kimi_share, chip_plan,
                                                 one_chip, on_chip):
    from repro.runtime.steps import make_prefill_step
    cfg, params = kimi_share
    step = jax.jit(make_prefill_step(cfg, chip_plan, 3584))
    compiled = step.lower(
        params, {"tokens": _sds((1, 1464), jnp.int32, one_chip)}).compile()
    m = _fits(compiled)
    assert m.temp_size_in_bytes < 2 * 10 ** 9, m
