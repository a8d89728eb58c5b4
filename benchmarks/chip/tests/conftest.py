import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
BENCH = HERE.parent
ROOT = BENCH.parents[1]
for p in (ROOT / "src", BENCH):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def cell_from_files(config, mix, name):
    """A cell made from a configuration and a mix file by their names, as
    ``harness.load_cell`` makes one from its ``BENCHMARK.json`` entry."""
    import json
    import harness
    import traffic
    mixed = traffic.load(mix)
    return harness.Cell(
        name=name, chips=1,
        config=json.loads((BENCH / "configs" / f"{config}.json").read_text()),
        traffic=mixed, path=mixed["path"], limits={},
        bench=json.loads((ROOT / "BENCHMARK.json").read_text()))


def tiny_serve_cell():
    """The serving cell cut to a size the CPU runs in seconds: every width
    small, the same engine, path, traffic generator and reference."""
    cell = cell_from_files("yi34b-s8", "chat", "serve.yi34b-s8.chat")
    c = cell.config
    c.update({"hidden_size": 64, "intermediate_size": 128,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "num_hidden_layers": 2, "vocab_size": 256})
    c["program"]["overrides"] = {
        "n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
        "head_dim": 16, "d_ff": 128, "vocab": 256, "n_kv_eff": 2,
        "q_block": 32, "kv_block": 32}
    cell.traffic.update({
        "prompt": {"ladder": [8, 16, 24], "median": 12, "sigma": 0.5},
        "output": {"median": 4, "sigma": 0.5, "min": 2, "max": 8},
        "cache_len": 32, "max_batch": 4})
    cell.traffic["rate_per_s"] = 6.0
    return cell
