"""The held-read share of the expert-share cell
(``serve.kimik2-s9e8.longout``): what share of the held layer-experts a
decode step read, from the engine's counters; nothing from a program that
does not count what its steps read."""

import types

import pytest

import harness

CELL = "serve.kimik2-s9e8.longout"


def _run(decode):
    return types.SimpleNamespace(trace=None, counters={
        "decode_tokens": 320, "moe": {"decode": decode}})


@pytest.mark.parametrize("decode,want", [
    # 10 steps of 8 expert layers of 8 held experts: 640 layer-experts
    ({"moe_assignments": 320 * 64, "moe_local_assignments": 427,
      "moe_experts_touched": 300, "moe_experts_read": 300, "steps": 10},
     100.0 * 300 / 640),
    ({"moe_assignments": 0, "moe_local_assignments": 0,
      "moe_experts_touched": 0, "moe_experts_read": 0, "steps": 10}, 0.0),
    # the parent's counters: no count of what was read
    ({"moe_assignments": 320 * 64, "moe_local_assignments": 427,
      "moe_experts_touched": 300, "steps": 10}, None),
    ({"moe_assignments": 0, "moe_local_assignments": 0,
      "moe_experts_touched": 0, "moe_experts_read": 0, "steps": 0}, None),
    (None, None),
])
def test_held_read_share_reads_the_engine_counter(decode, want):
    cell = harness.load_cell(CELL)
    reader = harness.load_module("metrics", "held_read_share.kimik2")
    got = reader.read(_run(decode), cell, {})
    assert got == (pytest.approx(want) if want is not None else None)
