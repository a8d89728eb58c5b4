"""``trace.py`` on a small trace recorded on one TPU v5e: a 32x1024 decode
program run 5 times and two prefill programs of 256 and 512 rows."""

import pathlib

import pytest

import harness

FIXTURE = pathlib.Path(__file__).parent / "fixtures" / \
    "decode_prefill.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    tr = harness.load_module(".", "trace")
    return tr.reduce(FIXTURE)


def test_window_and_busy_union(summary):
    assert summary.devices == 1
    assert summary.window_ns == 298176054.0
    runs = [r for rs in summary.programs.values() for r in rs]
    assert len(runs) == 15
    # programs run one at a time on the chip: the union is their sum
    assert summary.busy_ns == pytest.approx(sum(r.dur for r in runs))
    assert summary.busy_ns == 71440.0
    assert 0 < summary.busy_s < summary.window_s


def test_per_program_time_and_fingerprints(summary):
    assert sorted(summary.programs) == ["jit__decode", "jit__prefill"]
    assert summary.program_ns("jit__decode") == 9543.0
    pre = summary.programs["jit__prefill"]
    fps = {r.fingerprint for r in pre}
    assert len(fps) == 2             # one program per prompt length
    mean = {fp: sum(r.dur for r in pre if r.fingerprint == fp) / 5
            for fp in fps}
    assert max(mean.values()) / min(mean.values()) == pytest.approx(
        1.8, abs=0.1)                # 512 rows vs 256


def test_idle_between_decodes_is_what_no_program_covers(summary):
    idle = summary.idle_between("jit__decode")
    dec = summary.programs["jit__decode"]
    assert len(idle) == 4
    for a, b, gap in zip(dec, dec[1:], idle):
        others = sum(r.dur for r in summary.programs["jit__prefill"]
                     if a.start < r.start < b.start)
        assert gap == pytest.approx(b.start - a.start - a.dur - others)


def test_breakdown_is_bounded_and_named(summary):
    b = summary.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0] == "jit__prefill/fusion"
    assert all(isinstance(n, str) and s > 0 for n, s in b["idle_gaps"])


def test_a_trace_with_no_device_program_is_refused(tmp_path):
    import jax
    tr = harness.load_module(".", "trace")
    empty = tmp_path / "empty.xplane.pb"
    empty.write_bytes(jax.profiler.ProfileData.text_proto_to_serialized_xspace(
        'planes { name: "Task Environment" }'))
    with pytest.raises(ValueError):
        tr.reduce(empty)
