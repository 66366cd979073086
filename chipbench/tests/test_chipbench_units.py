"""Self-tests of the yardstick's pure parts: cycle and episode arithmetic on
synthetic timestamps, the trace reducer (synthetic and a recorded v5e slice),
FLOP and byte functions against hand counts, the comparison, and
``BENCHMARK.json`` against the contract's character rules.

    python -m pytest chipbench/tests -q
"""

import gzip
import json
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from chipbench import correct, cycles, families, flops, trace_reduce  # noqa: E402
from chipbench.families import gpt2  # noqa: E402
from chipbench.readers import read_metric  # noqa: E402


# -- cycles -------------------------------------------------------------------

def _saves(window_open, n, steps, period, call_s, commit_after):
    """n whole cycles: steps at ``period``, then a save call of ``call_s``."""
    saves, step_ends, t = [], [], window_open
    for _ in range(n):
        for _ in range(steps):
            t += period
            step_ends.append(t)
        saves.append({"call": t + 0.01, "ret": t + call_s,
                      "commit": t + call_s + commit_after})
        t += call_s
    return saves, step_ends


def test_goodput_counts_whole_cycles_only():
    saves, step_ends = _saves(100.0, 3, 10, 0.1, 0.5, 0.4)
    # the clock cut a fourth cycle after 7 steps: it left steps, but no save
    step_ends += [saves[-1]["ret"] + 0.1 * i for i in range(1, 8)]
    found = cycles.whole_cycles(100.0, saves, open_commit=100.3)
    assert len(found) == 3
    assert cycles.cycle_times(found) == pytest.approx([1.5, 1.5, 1.5])
    assert cycles.goodput_tokens_per_s(found, 10, 4096) == pytest.approx(10 * 4096 / 1.5)
    assert cycles.goodput_tokens_per_s([], 10, 4096) is None


def test_goodput_is_all_the_work_over_all_the_time_so_one_stalled_cycle_moves_it():
    found = [{"start": 0, "call": 0.9, "end": 1.0, "opening_commit": 0.2},
             {"start": 1.0, "call": 1.9, "end": 2.0, "opening_commit": 1.2},
             {"start": 2.0, "call": 6.9, "end": 7.0, "opening_commit": 2.2}]
    assert cycles.goodput_tokens_per_s(found, 5, 100) == pytest.approx(3 * 500 / 7.0)
    assert cycles.median(cycles.cycle_times(found)) == pytest.approx(1.0)  # it hides it


def test_step_periods_split_by_the_commit():
    saves, step_ends = _saves(100.0, 2, 10, 0.1, 0.5, 0.35)
    found = cycles.whole_cycles(100.0, saves, open_commit=100.35)
    split = cycles.split_step_periods(found, step_ends)
    assert len(split["draining"]) == 2 * 2       # steps at .1 .2 .3 -> 2 periods
    assert len(split["drain_free"]) == 2 * 6     # steps at .4 .. 1.0 -> 6 periods
    assert cycles.save_stall_s(found, 10, split["drain_free"]) == pytest.approx(0.5)


def test_a_save_not_committed_before_the_next_call_is_a_failed_operation():
    saves, _ = _saves(100.0, 3, 10, 0.1, 0.5, 0.4)
    saves[0]["commit"] = saves[1]["call"] + 0.01   # landed too late
    saves[1]["commit"] = None                       # never seen
    found = cycles.whole_cycles(100.0, saves, open_commit=100.2)
    assert cycles.uncommitted_saves(found) == 2
    assert cycles.uncommitted_saves(cycles.whole_cycles(100.0, saves[:1], 100.2)) == 0


def test_another_cycle_or_episode_only_if_it_fits():
    assert cycles.fits_another(now=10.0, deadline=51.0, last_duration=12.0, margin=1.2)
    assert not cycles.fits_another(now=40.0, deadline=51.0, last_duration=10.0, margin=1.2)


def test_episode_numbers_leave_out_what_did_not_recover():
    whole = {"freeze": 5.0, "trip": 5.25, "reenter": 6.0, "restore_start": 6.1,
             "restore_end": 16.1, "recovered": 16.2}
    cut = {"freeze": 30.0, "trip": 30.2, "reenter": 31.0, "restore_start": 31.1,
           "restore_end": 41.0}
    got = cycles.episode_numbers([whole, cut])
    assert got["recover_s"] == pytest.approx([11.2])
    assert got["detect_s"] == pytest.approx([0.25])
    assert got["abort_reenter_s"] == pytest.approx([0.75])
    assert got["restore_s"] == pytest.approx([10.0])


def test_readers_take_clean_cycles_where_there_are_any():
    saves, step_ends = _saves(100.0, 3, 10, 0.1, 0.5, 0.35)
    opener = {"call": 99.5, "ret": 100.0, "commit": 100.35, "in_window": False}
    R = {"loop": "steady_save", "window_open": 100.0, "steps_per_save": 10,
         "tokens_per_step": 4096, "bare_step_s": 0.1, "step_ends": step_ends,
         "saves": [opener] + [dict(s, in_window=True) for s in saves],
         "traced_cycles": [0, 1],
         "save_call_hist_at_open": {"count": 2, "sum_ns": 9e9},
         "save_call_hist_at_close": {"count": 5, "sum_ns": 10.5e9}}
    assert read_metric("end_to_end", "goodput_tokens_per_s", R) == pytest.approx(
        10 * 4096 / 1.5)
    assert read_metric("layer_metrics", "always_on_tax_pct", R) == pytest.approx(0.0, abs=1e-6)
    assert read_metric("layer_metrics", "save_stall_ms", R) == pytest.approx(500.0)
    assert read_metric("layer_metrics", "save_call_ms", R) == pytest.approx(500.0)
    assert read_metric("layer_metrics", "save_commit_s", R) == pytest.approx(0.84)
    assert read_metric("layer_metrics", "median_cycle_s", R) == pytest.approx(1.5)
    assert read_metric("layer_metrics", "restore_s", R) is None  # nothing to read


# -- trace reducer ------------------------------------------------------------------

def test_busy_union_and_gap_attribution_synthetic():
    ops = [["a", "", 0.0, 1.0], ["b", "", 0.5, 1.0],       # overlap: busy 0..1.5
           ["c", "", 2.0, 0.5],                              # gap 1.5..2.0
           ["d", "", 4.0, 1.0]]                              # gap 2.5..4.0
    spans = [["outer", 1.0, 3.5], ["inner", 2.4, 0.3], ["late", 4.5, 1.0]]
    assert trace_reduce.union([(0.0, 1.0), (0.5, 1.5), (2.0, 2.5)]) == [(0.0, 1.5), (2.0, 2.5)]
    assert trace_reduce.busy_seconds(ops, 0.0, 5.0) == pytest.approx(3.0)
    assert trace_reduce.busy_seconds(ops, 0.25, 2.25) == pytest.approx(1.5)
    gaps = trace_reduce.idle_gaps(ops, 0.0, 6.0, spans)
    assert gaps[0] == ["inner", pytest.approx(1.5)]   # innermost span at 2.5
    assert gaps[1] == ["late", pytest.approx(1.0)]    # 5.0..6.0
    assert gaps[2] == ["outer", pytest.approx(0.5)]   # 1.5..2.0
    assert trace_reduce.op_seconds(ops, 0.0, 3.0, top=2) == [["a", 1.0], ["b", 1.0]]
    assert trace_reduce.short_name(
        "%fusion.12 = f32[4,1024]{1,0} fusion(f32[4] %p), kind=kLoop") == "fusion.12"


@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(HERE, "data", "v5e_save_slice.json.gz")
    with gzip.open(path, "rt") as f:
        return json.load(f)


def test_recorded_v5e_slice(recorded):
    """A slice of a real trace (TPU v5 lite, gpt2-xl-1chip.steady-save, PR 23):
    two steps, the wait for the device, the save call with its fingerprint
    and snapshot copy, 1.25 s of idle device, and the first step after it."""
    dev = next(iter(recorded["trace"]["devices"].values()))
    spans = recorded["trace"]["spans"]
    lo = min(op[2] for op in dev["ops"])
    hi = max(op[2] + op[3] for op in dev["ops"])
    busy = trace_reduce.busy_seconds(dev["ops"], lo, hi)
    assert busy == pytest.approx(recorded["expect"]["busy_s"], rel=1e-9)
    assert 0 < busy < hi - lo
    everywhere = (float("-inf"), float("inf"))
    steps = trace_reduce.module_runs(dev["modules"], *everywhere, "jit_step")
    assert len(steps) == recorded["expect"]["n_steps"]
    copies = trace_reduce.module_runs(dev["modules"], *everywhere, "jit__lambda")
    assert len(copies) == 1
    copy_s = trace_reduce.ops_within(dev["ops"], copies)[0]
    # the copy cannot beat the roofline: 2 x state bytes at 819 GB/s
    least = flops.snapshot_copy_bytes(recorded["state_bytes"]) / 819e9
    assert least < copy_s < 2 * least
    gaps = trace_reduce.idle_gaps(dev["ops"], lo, hi, spans)
    assert gaps[0][0] in {s[0] for s in spans}
    assert sum(g[1] for g in gaps) <= (hi - lo) - busy + 1e-9


# -- FLOPs and bytes ----------------------------------------------------------------

def test_flops_against_hand_counts():
    sizes = gpt2.Sizes(name="hand", n_embd=4, n_head=2, n_layer=2, n_inner=8,
                       n_positions=6, vocab_size=10, rows=3, seq=6, feed_batches=1)
    # per layer: q,k,v,o 4 x (4x4) and two 4x8 matmuls = 128 MACs -> 256 FLOPs;
    # causal attention: scores and values, 2 x 2 x d x (T+1)/2 = 2*2*4*3.5 = 56
    # head: 2 x 10 x 4 = 80
    assert gpt2.forward_flops_per_token(sizes) == 2 * (256 + 56) + 80
    assert gpt2.train_flops_per_token(sizes) == 3 * (2 * (256 + 56) + 80)
    assert sizes.n_params == (10 + 6) * 4 + 2 * (4 * 16 + 2 * 32 + 8) + 4
    assert sizes.state_bytes == sizes.n_params * 14 + 4
    assert sizes.tokens_per_step == 18
    # one step of 18 tokens a second, 3 x 704 FLOPs a token, against 197e12
    assert flops.step_mfu_pct(gpt2, sizes, 1.0, "TPU v5 lite") == pytest.approx(
        100 * 3 * 704 * 18 / 197e12)
    assert flops.snapshot_copy_roofline_pct(819, 2.0, "TPU v5 lite") == pytest.approx(
        100 * (2 * 819 / 819e9) / 2.0)
    with pytest.raises(KeyError):
        flops.peaks("TPU v9")
    with pytest.raises(KeyError):
        flops.peaks("source")


def test_published_sizes_of_the_configurations():
    family, xl = families.of_file(os.path.join(ROOT, "chipbench/configs/gpt2-xl-1chip.json"))
    _, cb = families.of_file(os.path.join(ROOT, "chipbench/configs/cerebras-gpt-1.3b-1chip.json"))
    assert family is gpt2 is families.load("gpt2")
    assert (xl.n_embd, xl.n_head, xl.n_inner, xl.vocab_size, xl.seq) == (1600, 25, 6400, 50257, 1024)
    assert (cb.n_embd, cb.n_head, cb.n_inner, cb.vocab_size, cb.seq) == (2048, 16, 8192, 50257, 2048)
    assert xl.n_params == 327_836_800 and cb.n_params == 258_129_920
    assert xl.tokens_per_step == cb.tokens_per_step == 4096
    assert xl.state_bytes == 14 * xl.n_params + 4 and cb.state_bytes == 14 * cb.n_params + 4


# -- the comparison ---------------------------------------------------------------------

def test_worst_leaf_gap_is_a_gap_of_norms_over_the_larger_of_leaf_and_median():
    reference = [10.0, 1.0, 1e-6]
    program = [10.5, 1.0, 0.5]      # leaf 2 is all but zero in the reference
    got = correct.worst_leaf_gap(program, reference)
    assert got == {"gap": pytest.approx(0.5 - 1e-6), "leaf": 2}  # over the median, 1.0
    assert correct.worst_leaf_gap([10.0, 1.2, 1e-6], reference)["leaf"] == 1
    with pytest.raises(ValueError):
        correct.worst_leaf_gap([1.0], reference)


def test_within_refuses_a_number_over_its_limit_and_a_nan():
    limits = {"loss_gap": 1e-3, "grad_norm_gap": 1e-2}
    assert correct.within({"loss_gap": 1e-4, "grad_norm_gap": 1e-3, "x": 9}, limits)
    assert not correct.within({"loss_gap": 1e-4, "grad_norm_gap": 2e-2}, limits)
    assert not correct.within({"loss_gap": float("nan"), "grad_norm_gap": 0}, limits)


def test_every_configuration_has_a_limits_file_and_every_traffic_file_a_loop(bench):
    from chipbench import loops

    for c in bench["configs"]:
        for rehearsal in (False, True):
            assert set(correct.load_limits(c["name"], rehearsal)) == {
                "loss_gap", "grad_norm_gap", "change_norm_gap"}
    for w in bench["workloads"]:
        with open(os.path.join(ROOT, "chipbench/traffic", w["traffic"] + ".json")) as f:
            loop = loops.load(json.load(f)["loop"])
        assert callable(loop.enter) and callable(loop.tally)
        assert loop.TRACED_WINDOW["kind"] in ("device_ops", "spans")
    with pytest.raises(ValueError):
        loops.load("no_such_loop")


def test_only_the_segments_the_worker_recorded_are_unlinked(tmp_path, monkeypatch):
    from chipbench import run

    shm = tmp_path / "shm"
    shm.mkdir()
    for name in ("psm_mine", "psm_other"):
        (shm / name).write_bytes(b"x")
    (tmp_path / "shm_segments.txt").write_text("psm_mine\npsm_gone\n../psm_other\n")
    monkeypatch.setattr(run, "SHM_DIR", str(shm))
    assert run.drop_recorded_shm(str(tmp_path)) == 1
    assert sorted(p.name for p in shm.iterdir()) == ["psm_other"]
    assert run.drop_recorded_shm(str(tmp_path / "nowhere")) == 0


# -- BENCHMARK.json against the contract ------------------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_json_keys_names_and_units(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "chipbench/run.py"]
    assert bench["paths"] == ["chipbench"]
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    one_line = lambda s: 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s  # noqa: E731
    names = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and one_line(c["why"]) and one_line(c["source"])
        assert PATH.match(c["file"]) and c["file"].startswith("chipbench/")
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        names.add(c["name"])
    assert len(names) == len(bench["configs"])
    assert len({c["file"] for c in bench["configs"]}) == len(bench["configs"])
    cells = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and one_line(w["why"])
        assert w["config"] in names and w["chips"] in (1, 4)
        assert os.path.isfile(os.path.join(ROOT, "chipbench/traffic", w["traffic"] + ".json"))
        cells.add(w["name"])
    assert len(cells) == len(bench["workloads"])
    assert {w["config"] for w in bench["workloads"]} == names
    e2e = {}
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        assert set(m.get("workloads", cells)) <= cells
        e2e[m["name"]] = set(m.get("workloads", cells))
    assert "setup_s" in e2e and e2e["setup_s"] == cells
    layers = {}
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and one_line(m["layer"])
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert m["moves"] in e2e and m["name"] not in e2e
        assert set(m.get("workloads", e2e[m["moves"]])) <= e2e[m["moves"]]
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
        layers[m["name"]] = m
    assert len(layers) == len(bench["per_layer"])
    for cell in cells:  # setup_s, one more end-to-end metric, one per-layer metric
        assert sum(cell in v for v in e2e.values()) >= 2
        assert any(cell in m.get("workloads", cells) for m in layers.values())


def test_every_metric_is_a_file_of_its_own_found_by_name(bench):
    for kind, kind_dir in (("end_to_end", "end_to_end"), ("per_layer", "layer_metrics")):
        for m in bench[kind]:
            with open(os.path.join(ROOT, "chipbench", kind_dir, m["name"] + ".json")) as f:
                spec = json.load(f)
            assert spec["name"] == m["name"] and spec["unit"] == m["unit"]
            assert spec["better"] == m["better"] and spec["source"] == m["source"]
            if kind == "per_layer":
                assert spec["layer"] == m["layer"] and spec["moves"] == m["moves"]
            assert read_metric(kind_dir, m["name"], {"loop": "none"}) is None


def test_files_under_paths_are_named_from_the_allowed_characters():
    for base, _dirs, files in os.walk(os.path.join(ROOT, "chipbench")):
        if "/out" in base or "__pycache__" in base:
            continue
        for name in files:
            rel = os.path.relpath(os.path.join(base, name), ROOT)
            assert PATH.match(rel), rel
