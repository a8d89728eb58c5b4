"""The readers of the serving engine's spans, on a small trace built by
hand: four decode programs and one prefill on the chip, and the tick
loop's and the prefill farm's spans on three host threads.  Every metric
is worked out below from the times, in microseconds."""

import types

import pytest

import harness

US = 1_000_000                           # picoseconds in a microsecond
WINDOW_US = 1000

# the chip: (program, start, end)
PROGRAMS = [("jit__decode(11)", 10, 110), ("jit__decode(11)", 150, 250),
            ("jit__prefill(22)", 260, 280), ("jit__decode(11)", 300, 400),
            ("jit__decode(11)", 430, 530)]
# idle between decodes: [110,150) 40; [250,260) + [280,300) 30; [400,430) 30
DECODE_GAP_US = (40 + 30 + 30) / 3

# the tick loop: (thread, name, start, end, stats)
TICK = [
    (0, "serve.decode", 5, 12, {"step": 0, "batch": 1}),
    (1, "serve.sync", 100, 115, {"step": 0}),
    (0, "serve.insert", 112, 118, {"req": 1, "slot": 0, "ready_us": 3000}),
    (1, "serve.collect", 115, 122, {"step": 0, "finished": 0}),
    (0, "serve.decode", 120, 130, {"step": 1, "batch": 2}),
    (0, "PjitFunction(_decode)", 121, 129, {}),
    (1, "np.asarray(jax.Array)", 140, 148, {}),     # no engine span
    (1, "serve.sync", 245, 255, {"step": 1}),
    (1, "serve.collect", 255, 258, {"step": 1, "finished": 1}),
    (0, "serve.decode", 290, 295, {"step": 2, "batch": 1}),
    (1, "serve.sync", 398, 405, {"step": 2}),
    (1, "serve.collect", 405, 407, {"step": 2, "finished": 0}),
    (0, "serve.insert", 410, 414, {"req": 2, "slot": 1, "ready_us": 500}),
    (0, "serve.decode", 420, 428, {"step": 3, "batch": 2}),
]
# the idle between decodes that no tick span covers: [110,150) less the
# union [100,130) is 20; [250,260) less [245,258) is 2 and [280,300) less
# [290,295) is 15; [400,430) less 5 + 2 + 4 + 8 is 11
HOP_GAP_US = (20 + 17 + 11) / 3
# insert + decode + collect per decode span: (10 + 30 + 12) / 4
TICK_HOST_US = 52 / 4
# a prefill thread: 20 prefills queued 100, 200, ... 2000 us, in the
# window's idle tail, where they cover no gap between decodes
PREFILLS = [(2, "serve.prefill", 535 + 20 * i, 545 + 20 * i,
             {"req": 10 + i, "len": 512, "queued_us": 100 * (i + 1)})
            for i in range(20)]


def _xspace(host_events) -> bytes:
    import jax
    names, stats = {}, {}

    def mid(table, name):
        return table.setdefault(name, len(table) + 1)

    def meta(table, kind):
        return "".join(f' {kind} {{ key: {i} value {{ id: {i} name: "{n}" '
                       f'}} }}' for n, i in table.items())

    dev = "".join(
        f" events {{ metadata_id: {mid(names, n)} offset_ps: {a * US} "
        f"duration_ps: {(b - a) * US} }}" for n, a, b in PROGRAMS)
    planes = [f'planes {{ id: 1 name: "/device:TPU:0" lines {{ id: 1 '
              f'name: "XLA Modules" timestamp_ns: 0{dev} }}'
              f'{meta(names, "event_metadata")} }}']
    names = {}
    lines = ""
    for t in sorted({e[0] for e in host_events}):
        evs = ""
        for _, n, a, b, st in (e for e in host_events if e[0] == t):
            sts = "".join(f" stats {{ metadata_id: {mid(stats, k)} "
                          f"int64_value: {v} }}" for k, v in st.items())
            evs += (f" events {{ metadata_id: {mid(names, n)} offset_ps: "
                    f"{a * US} duration_ps: {(b - a) * US}{sts} }}")
        lines += f' lines {{ id: {t + 1} name: "t{t}" timestamp_ns: 0{evs} }}'
    planes.append(f'planes {{ id: 2 name: "/host:CPU"{lines}'
                  f'{meta(names, "event_metadata")}'
                  f'{meta(stats, "stat_metadata")} }}')
    planes.append(
        'planes { id: 3 name: "Task Environment" '
        'stats { metadata_id: 1 uint64_value: 5000 } '
        f'stats {{ metadata_id: 2 uint64_value: {5000 + WINDOW_US * 1000} }} '
        'stat_metadata { key: 1 value { id: 1 name: "profile_start_time" } }'
        ' stat_metadata { key: 2 value { id: 2 name: "profile_stop_time" } }'
        ' }')
    return jax.profiler.ProfileData.text_proto_to_serialized_xspace(
        " ".join(planes))


def _traced(tmp_path, host_events, name):
    """A traced run and its cell, with the profile where ``run.py`` leaves
    it (``<checkout>/.bench_trace/<cell>``)."""
    cell = types.SimpleNamespace(root=tmp_path / "benchmarks" / "chip",
                                 name=name)
    d = tmp_path / ".bench_trace" / name / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(_xspace(host_events))
    tr = harness.load_module(".", "trace")
    run = types.SimpleNamespace(trace=tr.reduce(d / "host.xplane.pb"),
                                counters={})
    return run, cell


def _read(metric, run, cell):
    return harness.load_module("metrics", metric).read(run, cell, {})


@pytest.fixture
def spanned(tmp_path):
    return _traced(tmp_path, TICK + PREFILLS, "serve.spans")


def test_the_trace_holds_what_the_numbers_below_assume(spanned):
    run, _ = spanned
    assert [r.start for r in run.trace.programs["jit__decode"]] == \
        [10e3, 150e3, 300e3, 430e3]
    assert _read("decode_gap_ms.serve", *spanned) == \
        pytest.approx(DECODE_GAP_US * 1e-3)


def test_hop_gap_is_the_idle_no_tick_span_covers(spanned):
    hop = _read("hop_gap_ms.serve", *spanned)
    assert hop == pytest.approx(HOP_GAP_US * 1e-3)
    assert hop <= _read("decode_gap_ms.serve", *spanned)


def test_tick_host_is_tick_span_time_per_decode(spanned):
    assert _read("tick_host_ms.serve", *spanned) == \
        pytest.approx(TICK_HOST_US * 1e-3)


def test_prefill_queue_is_the_95th_percentile_of_queued_us(spanned):
    # nearest rank over 20 waits of 0.1 ... 2.0 ms: the 19th
    assert _read("prefill_queue_ms_p95.chat", *spanned) == \
        pytest.approx(1.9)


def test_ready_wait_is_the_95th_percentile_of_ready_us(spanned):
    # nearest rank over 0.5 and 3.0 ms: the 2nd
    assert _read("ready_wait_ms_p95.chat", *spanned) == pytest.approx(3.0)


def test_spans_keep_their_times_and_stats(spanned):
    import spans
    got = spans.of_run(*spanned)
    assert len(got) == 12 + 20           # serve.* only, in start order
    assert [s.start for s in got] == sorted(s.start for s in got)
    first = got[0]
    assert (first.name, first.start, first.dur, first.stats) == \
        ("serve.decode", 5e3, 7e3, {"step": 0, "batch": 1})
    assert [s.stats["queued_us"] for s in got
            if s.name == "serve.prefill"] == [100 * i for i in range(1, 21)]


def test_the_breakdown_names_a_gap_by_the_engine_span_in_it(spanned):
    run, _ = spanned
    gaps = dict((round(d * 1e9), n)
                for n, d in run.trace.breakdown()["idle_gaps"])
    # [110,150): decode covers 10 us of it, more than any other event
    assert gaps[40_000] == "serve.decode"


@pytest.mark.parametrize("metric", [
    "prefill_queue_ms_p95.chat", "ready_wait_ms_p95.chat",
    "tick_host_ms.serve", "hop_gap_ms.serve"])
def test_a_program_without_spans_reads_nothing(tmp_path, metric):
    # the program before it wrote spans: JAX's own host events only
    run, cell = _traced(tmp_path, [e for e in TICK
                                   if not e[1].startswith("serve.")],
                        "serve.nospans")
    assert _read(metric, run, cell) is None
    assert _read("decode_gap_ms.serve", run, cell) == \
        pytest.approx(DECODE_GAP_US * 1e-3)
    untraced = types.SimpleNamespace(trace=None, counters={})
    assert _read(metric, untraced, cell) is None
