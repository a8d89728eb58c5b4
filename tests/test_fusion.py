"""Device-segment fusion (core/fuse.py + the a2a_fused Pallas kernel):

- fused-vs-unfused byte-identical outputs on pipeline / farm / all_to_all /
  wrap_around device graphs, and on a hybrid graph where a host process farm
  feeds a fused device segment;
- the one-program-per-run invariant: N adjacent device stages lower to
  exactly ONE boundary node (hybrid) / ONE runner part (all-device);
- kernels/a2a_fused.py vs the kernels/ref.py oracle, bit-for-bit, across
  dtypes, block sizes, and capacity-overflow edges;
- the jitted-segment cache: re-compile() of the same graph reuses the
  traced program.
"""

import numpy as np
import pytest

from repro.core import FFNode, all_to_all, farm, pipeline
from repro.core.fuse import (FusedSegment, fuse_device_segments,
                             segment_cache_clear, segment_cache_info)


class Gen(FFNode):
    def __init__(self, n):
        super().__init__()
        self.i, self.n = 0, n

    def svc(self, _):
        self.i += 1
        return np.float32(self.i) if self.i <= self.n else None


# module-level (picklable) stages for the process-tier hybrid test
def _proc_affine(x):
    return x * 2.0 + 1.0


def _bytes(out):
    return [np.asarray(y).tobytes() for y in out]


def _device_entries(r):
    st = r.stats()
    stages = st.get("stages") or st.get("graph", {}).get("stages", [])
    return [s for s in stages if s.get("backend") == "device"]


def _dev_stages():
    import jax.numpy as jnp
    return [lambda x: x * 1.5 + 0.25,
            lambda x: jnp.tanh(x),
            lambda x: x - 0.125,
            lambda x: x * x + x]


# ---------------------------------------------------------------------------
# fused vs unfused: byte-identical outputs
# ---------------------------------------------------------------------------
def test_pipeline_device_fused_unfused_parity(plan):
    xs = [np.linspace(-1.0, 1.0, 16, dtype=np.float32) * (i + 1)
          for i in range(7)]
    a = pipeline(*_dev_stages()).compile(plan, mode="device").run(xs)
    b = pipeline(*_dev_stages()).compile(plan, mode="device",
                                         fuse=False).run(xs)
    assert _bytes(a) == _bytes(b)


def test_farm_in_pipeline_device_fused_unfused_parity(plan):
    xs = [np.float32(i) * 0.5 for i in range(9)]
    def build():
        return pipeline(lambda x: x + 1.0, farm(lambda x: x * 3.0, n=2),
                        lambda x: x - 0.5)
    a = build().compile(plan, mode="device").run(xs)
    b = build().compile(plan, mode="device", fuse=False).run(xs)
    assert _bytes(a) == _bytes(b)


def test_a2a_in_pipeline_device_fused_unfused_parity(plan):
    xs = [np.float32(i) for i in range(8)]
    def build():
        return pipeline(lambda x: x + 1.0,
                        all_to_all([lambda x: x * 10.0],
                                   [lambda y: y * 2.0, lambda y: y + 7.0]),
                        lambda y: y - 0.25)
    a = build().compile(plan, mode="device").run(xs)
    b = build().compile(plan, mode="device", fuse=False).run(xs)
    assert _bytes(a) == _bytes(b)


def test_wrap_around_device_fused_unfused_parity(plan):
    xs = [np.float32(i) for i in range(5)]
    def build():
        return pipeline(lambda x: x * 0.5 + 1.0).wrap_around()
    a = build().compile(plan, mode="device", feedback_steps=4).run(xs)
    b = build().compile(plan, mode="device", feedback_steps=4,
                        fuse=False).run(xs)
    assert _bytes(a) == _bytes(b)


@pytest.mark.shm
def test_hybrid_process_farm_feeds_fused_device_segment(plan):
    """Thread gen -> process farm -> fused device segment, one graph."""
    n = 12
    d1, d2, d3 = (lambda x: x * 1.25, lambda x: x + 0.5,
                  lambda x: x * x - 1.0)

    def build():
        return pipeline(Gen(n), farm(_proc_affine, n=2), d1, d2, d3)

    def compiled(fuse):
        # normalize=False: the optimizer would fold the trailing pure maps
        # into the farm collector, and this test needs them as distinct
        # top-level device stages for the fusion pass to merge
        return build().compile(
            plan, device_batch=4, fuse=fuse, normalize=False,
            placements={1: "host_process", 2: "device", 3: "device",
                        4: "device"})

    rf = compiled(True)
    targets = [p.target for _, p in rf.placements]
    assert targets == ["host", "host_process", "device", "device", "device"]
    a = sorted(_bytes(rf.run()))
    ru = compiled(False)
    b = sorted(_bytes(ru.run()))
    assert a == b
    # the fused run is one boundary node, the unfused one is three
    dev = _device_entries(rf)
    assert len(dev) == 1 and " + " in dev[0]["node"]
    assert len(_device_entries(ru)) == 3


# ---------------------------------------------------------------------------
# one program per device run
# ---------------------------------------------------------------------------
def test_adjacent_device_stages_lower_to_one_node(plan):
    """N adjacent device stages -> exactly ONE _DeviceStageNode."""
    s1, s2, s3, s4 = _dev_stages()
    r = pipeline(Gen(8), s1, s2, s3, s4).compile(
        plan, device_batch=4,
        placements={1: "device", 2: "device", 3: "device", 4: "device"})
    out = r.run()
    assert len(out) == 8
    dev = _device_entries(r)
    assert len(dev) == 1
    assert dev[0]["node"].count(" + ") == 3      # all four stages listed


def test_non_adjacent_device_runs_stay_separate(plan):
    s1, s2, s3, _ = _dev_stages()
    r = pipeline(Gen(6), s1, lambda x: float(x) + 0.0, s2, s3).compile(
        plan, device_batch=2,
        placements={1: "device", 2: "host", 3: "device", 4: "device"})
    assert len(r.run()) == 6
    assert len(_device_entries(r)) == 2          # [s1], host, [s2 + s3]


def test_all_device_graph_is_one_part(plan):
    r = pipeline(*_dev_stages()).compile(plan, mode="device")
    r.run([np.float32(1.0), np.float32(2.0)])
    st = r.stats()
    assert st["backend"] == "DeviceRunner"
    assert len(st["stages"]) == 1
    assert st["stages"][0]["node"].count(" + ") == 3


def test_fuse_pass_grouping_unit():
    import dataclasses

    @dataclasses.dataclass
    class P:
        target: str
        width: int = 1
        reason: str = "r"

    class S:
        def describe(self):
            return "s"

    stages = [S(), S(), S(), S(), S()]
    pl = [P("host"), P("device"), P("device"), P("host"), P("device")]
    grouped = fuse_device_segments(stages, pl)
    kinds = [type(e).__name__ for e, _ in grouped]
    assert kinds == ["S", "FusedSegment", "S", "FusedSegment"]
    assert len(grouped[1][0].stages) == 2
    assert grouped[1][1].reason.startswith("fused run of 2")
    off = fuse_device_segments(stages, pl, enable=False)
    assert all(isinstance(e, FusedSegment) and len(e.stages) == 1
               for e, p in off if p.target == "device")


def test_ffmap_device_lowering_fuses(plan):
    """A pure-splitter ffmap folds into the fused segment as a vmapped
    body (new device capability: host ffmap needs multi-emit nodes)."""
    import jax.numpy as jnp
    from repro.core import ffmap

    def split(x):
        return (x[:4], x[4:])

    def comp(parts):
        return jnp.concatenate(parts)

    def build():
        return pipeline(lambda x: x + 1.0,
                        ffmap(split, [lambda p: p * 2.0,
                                      lambda p: p - 3.0], comp),
                        lambda y: y * 0.5)
    xs = [np.arange(8, dtype=np.float32) * (i + 1) for i in range(5)]
    a = build().compile(plan, mode="device").run(xs)
    b = build().compile(plan, mode="device", fuse=False).run(xs)
    assert _bytes(a) == _bytes(b)
    expect = (np.concatenate([(xs[0] + 1.0)[:4] * 2.0,
                              (xs[0] + 1.0)[4:] - 3.0]) * 0.5)
    np.testing.assert_allclose(np.asarray(a[0]), expect, rtol=1e-6)


def test_ffmap_device_rejects_stateful_splitter(plan):
    from repro.core import GraphError, ffmap

    class Split(FFNode):
        def svc(self, t):
            self.ff_send_out(t)
            return None

    g = pipeline(ffmap(Split(), [lambda p: p], lambda parts: parts[0]))
    with pytest.raises(GraphError, match="pure splitter"):
        g.compile(plan, mode="device")


# ---------------------------------------------------------------------------
# the fused a2a kernel vs its oracle (bit-for-bit)
# ---------------------------------------------------------------------------
@pytest.mark.kernels
@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int32"])
@pytest.mark.parametrize("cap_kind", ["lossless", "overflow", "tight"])
def test_a2a_fused_matches_ref(dtype, cap_kind, rng):
    import jax
    import jax.numpy as jnp
    from repro.kernels.a2a_fused import a2a_fused
    from repro.kernels.ref import a2a_fused_ref

    T, E, D = 32, 3, 5
    k1, k2 = jax.random.split(rng)
    logits = jax.random.normal(k1, (T, E), jnp.float32)
    if dtype == "int32":
        xs = jax.random.randint(k2, (T, D), -50, 50, jnp.int32)
        fns = tuple((lambda x, s=j + 2: x * s + s) for j in range(E))
    else:
        xs = jax.random.normal(k2, (T, D)).astype(dtype)
        fns = tuple((lambda x, s=float(j + 1): x * s - s) for j in range(E))
    cap = {"lossless": T, "overflow": max(1, T // E - 3),
           "tight": 1}[cap_kind]
    out, keep = a2a_fused(logits, xs, fns, cap, block_t=8, interpret=True)
    # jit the oracle too: production always runs both inside a jitted
    # segment, and eager-mode op-by-op rounding differs from ANY jitted
    # program by FMA contraction (a 1-ulp artifact of eager mode, not of
    # the kernel)
    import functools
    ro, rk = jax.jit(functools.partial(a2a_fused_ref, expert_fns=fns,
                                       capacity=cap))(logits, xs)
    assert out.dtype == ro.dtype
    assert np.asarray(out).tobytes() == np.asarray(ro).tobytes()
    assert np.array_equal(np.asarray(keep), np.asarray(rk))
    if cap_kind == "lossless":
        assert bool(np.all(np.asarray(keep)))
    else:
        assert not bool(np.all(np.asarray(keep)))      # some tokens dropped
        assert np.all(np.asarray(out)[~np.asarray(keep)] == 0)


@pytest.mark.kernels
def test_a2a_fused_scalar_output_experts(rng):
    import jax
    import jax.numpy as jnp
    from repro.kernels.a2a_fused import a2a_fused
    from repro.kernels.ref import a2a_fused_ref

    T, E = 16, 2
    k1, k2 = jax.random.split(rng)
    logits = jax.random.normal(k1, (T, E))
    xs = jax.random.normal(k2, (T, 4))
    fns = (lambda x: jnp.sum(x), lambda x: jnp.prod(x))
    import functools
    out, keep = a2a_fused(logits, xs, fns, T, interpret=True)
    ro, rk = jax.jit(functools.partial(a2a_fused_ref, expert_fns=fns,
                                       capacity=T))(logits, xs)
    assert out.shape == (T,)
    assert np.asarray(out).tobytes() == np.asarray(ro).tobytes()


@pytest.mark.kernels
@pytest.mark.parametrize("T,block_t,want", [(200, 128, 40), (4096, 128, 128),
                                            (12, 128, 12), (36, 32, 36),
                                            (7, 4, 7)])
def test_a2a_fused_block_is_sublane_tiled(T, block_t, want):
    """A token block the TPU accepts: a multiple of 8 that divides T, or T
    itself (T=200 used to give 100)."""
    from repro.kernels.a2a_fused import _pick_block
    assert _pick_block(T, block_t) == want


@pytest.mark.kernels
def test_a2a_fused_rejects_mismatched_experts(rng):
    import jax
    import jax.numpy as jnp
    from repro.kernels.a2a_fused import a2a_fused

    logits = jax.random.normal(rng, (8, 2))
    xs = jax.random.normal(rng, (8, 4))
    with pytest.raises(ValueError, match="agree on output"):
        a2a_fused(logits, xs, (lambda x: x, lambda x: jnp.sum(x)), 8)
    with pytest.raises(ValueError, match="experts"):
        a2a_fused(logits, xs, (lambda x: x,), 8)


# ---------------------------------------------------------------------------
# the jitted-segment cache
# ---------------------------------------------------------------------------
def test_recompile_reuses_jitted_segment(plan):
    segment_cache_clear()
    g = pipeline(*_dev_stages())
    xs = [np.float32(1.0), np.float32(2.0)]
    a = g.compile(plan, mode="device").run(xs)
    assert segment_cache_info()["misses"] >= 1
    before = segment_cache_info()["hits"]
    b = g.compile(plan, mode="device").run(xs)   # the Supervisor's re-place
    assert segment_cache_info()["hits"] > before
    assert _bytes(a) == _bytes(b)


def test_distinct_graphs_do_not_share_segments(plan):
    segment_cache_clear()
    xs = [np.float32(3.0)]
    a = pipeline(lambda x: x + 1.0).compile(plan, mode="device").run(xs)
    b = pipeline(lambda x: x + 2.0).compile(plan, mode="device").run(xs)
    assert float(a[0]) == 4.0 and float(b[0]) == 5.0
    assert segment_cache_info()["size"] >= 2
