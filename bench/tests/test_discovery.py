"""A cell, a configuration, a traffic mix and a per-layer metric are added
by adding files and entries only: a copy of the benchmark with one more of
each resolves the new cell with no existing file edited."""

import json
import shutil
from pathlib import Path

import harness

BENCH = Path(harness.__file__).resolve().parent


def test_new_cell_is_picked_up(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns(".trace", "__pycache__"))
    shutil.copy(BENCH.parent / "BENCHMARK.json", root / "BENCHMARK.json")
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}

    # new files
    conf = json.loads((root / "bench/configs/yi-34b.json").read_text())
    conf["num_hidden_layers"] = 4
    (root / "bench/configs/yi-34b-4l.json").write_text(json.dumps(conf))
    mix = json.loads((root / "bench/traffic/chat.json").read_text())
    mix["arrivals"]["rate_per_s"] = 0.25
    (root / "bench/traffic/chat-slow.json").write_text(json.dumps(mix))
    (root / "bench/limits/yi34b4.chat-slow.json").write_text(
        json.dumps({"max_logit_gap": 0.5}))
    (root / "bench/metrics/decode_share.py").write_text(
        "def read(run):\n    return 42.0\n")
    # new entries, appended
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "yi-34b-4l", "source": "x",
                            "file": "bench/configs/yi-34b-4l.json",
                            "reduced": ["num_hidden_layers"], "why": "x"})
    spec["workloads"].append({"name": "yi34b4.chat-slow",
                              "config": "yi-34b-4l", "traffic": "chat-slow",
                              "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "decode_share", "unit": "%",
                              "better": "higher", "source": "host_clock",
                              "layer": "model step", "moves": "itl_p95_ms"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.load_cell(root, "yi34b4.chat-slow")
    assert cell.conf["num_hidden_layers"] == 4
    assert cell.mix["arrivals"]["rate_per_s"] == 0.25
    assert cell.limits == {"max_logit_gap": 0.5}
    names = [m["name"] for m in cell.per_layer]
    assert "decode_share" in names and "step_mfu" not in names
    assert harness.reader("decode_share", cell.bench_dir)(None) == 42.0
    assert {m["name"] for m in cell.end_to_end} == {"itl_p95_ms", "setup_s"}
    # the old cells still resolve as before, and no file was edited
    assert harness.load_cell(root, "yi34b.chat").conf["num_hidden_layers"] \
        == 6
    for p, data in before.items():
        if p.name != "BENCHMARK.json":
            assert p.read_bytes() == data, p


def test_new_traffic_shapes_are_data(tmp_path):
    """On/off bursts and two length populations in one queue are mix files
    the one generator reads: the cells that use them resolve and plan with
    no existing file edited."""
    import traffic
    from test_traffic import BURSTY, MIXED
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns(".trace", "__pycache__"))
    shutil.copy(BENCH.parent / "BENCHMARK.json", root / "BENCHMARK.json")
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    spec = json.loads((root / "BENCHMARK.json").read_text())
    for mix_name, mix in (("chat-bursty", BURSTY), ("mixed-length", MIXED)):
        (root / f"bench/traffic/{mix_name}.json").write_text(json.dumps(mix))
        spec["workloads"].append({"name": f"yi34b.{mix_name}",
                                  "config": "yi-34b", "traffic": mix_name,
                                  "chips": 1, "why": "x"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    bursty = harness.load_cell(root, "yi34b.chat-bursty")
    due = [p.due_s for p in traffic.generate(bursty.mix, 11, 51, 64000)]
    assert due and all(int(t // 5) % 2 == 0 for t in due)
    mixed = harness.load_cell(root, "yi34b.mixed-length")
    lens = [len(p.prompt) for p in traffic.generate(mixed.mix, 11, 51, 64000)]
    assert max(lens) >= 2560 and min(lens) <= 320
    assert harness.decode_widths(mixed)[-1] >= -(-(3584 + 256) // 16)
    for p, data in before.items():
        if p.name != "BENCHMARK.json":
            assert p.read_bytes() == data, p
