"""A cell, a configuration, a traffic mix, a per-layer metric and a model
family are added by adding files and entries only: a copy of the benchmark
with one more of each resolves the new cell with no existing file edited."""

import json
import re
import shutil
from pathlib import Path

import pytest

import harness
from conftest import TINY_CONF, TINY_MIX

BENCH = Path(harness.__file__).resolve().parent

# a model family of its own: the Llama module's functions, each call
# recorded, found by the model_type "spyfam"
SPY_MODEL = """
from pathlib import Path

import harness

_llama = harness.model_module("llama", Path(__file__).resolve().parents[1])
CALLS = []
PAGED_ATTN_KERNELS = _llama.PAGED_ATTN_KERNELS


def _recorded(name):
    def call(*args, **kwargs):
        CALLS.append(name)
        return getattr(_llama, name)(*args, **kwargs)
    return call


for _name in ("arch_config", "make_params", "served_logits", "model_flops",
              "attn_flops", "attn_bytes", "attn_layers", "site_layers"):
    globals()[_name] = _recorded(_name)
"""


def _checkout(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns(".trace", "__pycache__"))
    shutil.copy(BENCH.parent / "BENCHMARK.json", root / "BENCHMARK.json")
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    return root, before


def _unedited(root, before):
    """No file that was there changed, and BENCHMARK.json only gained
    entries at the ends of its lists."""
    for p, data in before.items():
        if p.name != "BENCHMARK.json":
            assert p.read_bytes() == data, p
    old = json.loads(before[root / "BENCHMARK.json"])
    new = json.loads((root / "BENCHMARK.json").read_text())
    assert set(new) == set(old)
    for key, value in old.items():
        if isinstance(value, list):
            assert new[key][:len(value)] == value, key
        else:
            assert new[key] == value, key


def _add_cell(root, model_type, cell):
    """A tiny configuration of ``model_type``, its mix, limits and cell."""
    conf = dict(TINY_CONF, model_type=model_type, name=f"tiny-{model_type}")
    (root / f"bench/configs/tiny-{model_type}.json").write_text(
        json.dumps(conf))
    (root / "bench/traffic/tiny.json").write_text(json.dumps(TINY_MIX))
    (root / f"bench/limits/{cell}.json").write_text(
        json.dumps({"max_logit_gap": 1e-3}))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": f"tiny-{model_type}", "source": "x",
                            "file": f"bench/configs/tiny-{model_type}.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": cell, "config": f"tiny-{model_type}",
                              "traffic": "tiny", "chips": 1, "why": "x"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


def test_new_cell_is_picked_up(tmp_path):
    root, before = _checkout(tmp_path)

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
    _unedited(root, before)


def test_new_traffic_shapes_are_data(tmp_path):
    """On/off bursts and two length populations in one queue are mix files
    the one generator reads: the cells that use them resolve and plan with
    no existing file edited."""
    import traffic
    from test_traffic import BURSTY, MIXED
    root, before = _checkout(tmp_path)
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
    _unedited(root, before)


def test_new_model_family_is_picked_up(tmp_path):
    """A configuration whose model_type names a module that a PR adds:
    the harness makes its weights, serves it, checks it against its
    reference and counts its work through that module alone."""
    import gc
    root, before = _checkout(tmp_path)
    (root / "bench/models/spyfam.py").write_text(SPY_MODEL)
    _add_cell(root, "spyfam", "spy.tiny")

    cell = harness.load_cell(root, "spy.tiny")
    assert Path(cell.model.__file__) == root / "bench/models/spyfam.py"
    calls = cell.model.CALLS
    cfg, engine, plan = harness.setup(cell, 3, 2.0)
    assert calls == ["arch_config", "make_params"]
    run = harness.RunData(cell, cfg, harness.peaks_for("TPU v5 lite"))
    harness._spy(engine, run)
    harness.drive(engine, plan, 2.0, run)
    picked = harness.sample(run, 3)
    del engine
    gc.collect()
    got = harness.compare(cell.model, cfg, 3, picked)
    assert calls[2:] == ["served_logits"]
    assert got["max_logit_gap"] <= cell.limits["max_logit_gap"]
    assert harness.reader("step_mfu", cell.bench_dir)(run) > 0
    assert set(calls[3:]) == {"model_flops"}

    # the cells that were there still take the Llama module
    for name in ("yi34b.chat", "dsk7b.longdoc-batch"):
        old = harness.load_cell(root, name).model
        assert Path(old.__file__) == root / "bench/models/llama.py"
    _unedited(root, before)


def test_unknown_model_type_names_the_missing_module(tmp_path):
    root, before = _checkout(tmp_path)
    _add_cell(root, "nosuchfamily", "nosuch.tiny")
    missing = root / "bench/models/nosuchfamily.py"
    with pytest.raises(harness.BenchError, match=re.escape(str(missing))):
        harness.load_cell(root, "nosuch.tiny")
    _unedited(root, before)
