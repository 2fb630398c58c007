"""The harness finds every part of a cell by name, refuses a device it
has no peaks for, counts the step's bytes from the configuration, and
draws the traffic a mix states from the seed."""
import collections
import dataclasses
import json
import math
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import cells, harness, loadgen
from chipbench.tests import tiny

REPO = Path(__file__).resolve().parents[2]
BENCH = cells.load_benchmark(REPO)


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves(name):
    cell = cells.resolve(REPO, BENCH, name)
    assert cell.config and cell.traffic
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(cell.reader(m["name"]))
        assert m["moves"] in names


def test_config_files_match_their_entries():
    for c in BENCH["configs"]:
        cfg = cells.load_json(REPO / c["file"])
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert set(c["reduced"]) == set(cfg["reduced"])


def test_a_cell_added_as_data_is_found(tmp_path):
    root = tiny.build(tmp_path)
    mix = {"loop": "closed", "clients": 3, "r": {"5": 1}, "steps": {"2": 1},
           "snapshot_every": {"0": 1}, "deadline_s": 60.0}
    (root / "chipbench" / "traffic" / "tiny-c3.json").write_text(
        json.dumps(mix))
    bench = cells.load_benchmark(root)
    bench["workloads"].append({"name": "tiny-life-c3", "config": "tiny-life",
                               "traffic": "tiny-c3", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("tiny-life-c3")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = cells.resolve(root, cells.load_benchmark(root), "tiny-life-c3")
    assert cell.traffic == mix and cell.config["m"] == 2
    assert {m["name"] for m in cell.per_layer} == {
        "device_idle_share.batch", "step_hbm_share"}
    with pytest.raises(cells.CellError):
        cells.resolve(root, bench, "no-such-cell")


def test_a_metric_without_its_reader_is_refused(tmp_path):
    root = tiny.build(tmp_path)
    (root / "chipbench" / "metrics" / "step_hbm_share.py").unlink()
    with pytest.raises(cells.CellError, match="step_hbm_share"):
        cells.resolve(root, cells.load_benchmark(root), "tiny-life-batch")


def test_a_mix_that_is_not_a_closed_loop_is_refused(tmp_path):
    root = tiny.build(tmp_path)
    cell = cells.resolve(root, cells.load_benchmark(root), "tiny-life-batch")
    cell.traffic = dict(cell.traffic, loop="open")
    with pytest.raises(cells.CellError, match="closed"):
        harness.run_cell(cell, 1, 0.1, False, 0.0, need_tpu=False)


def test_cache_stays_in_the_checkout(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/shared")
    got = harness.use_compile_cache(tmp_path)
    assert got == str(tmp_path / ".jax_cache")
    import os
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == got


class _Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


def _jax(platform, kind, n=1):
    return SimpleNamespace(devices=lambda: [_Dev(platform, kind)] * n)


PEAKS = cells.load_json(REPO / harness.PEAKS)


@pytest.mark.parametrize("platform, kind, n, chips", [
    ("cpu", "cpu", 1, 1),
    ("gpu", "TPU v5 lite", 1, 1),
    ("tpu", "TPU v9 unknown", 1, 1),
    ("tpu", "TPU v5 lite", 1, 4),
])
def test_device_without_peaks_or_tpu_fails(platform, kind, n, chips):
    with pytest.raises(harness.DeviceError):
        harness.device_info(_jax(platform, kind, n), chips, PEAKS)


def test_known_tpu_passes():
    dev = harness.device_info(_jax("tpu", "TPU v5 lite", 4), 4, PEAKS)
    assert dev == {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}


def test_measurement_path_exits_without_result_on_cpu(capsys):
    args = SimpleNamespace(workload="life-r17-batch4", seed=2 ** 31 + 5,
                           seconds=1.0, trace=0)
    assert harness.main(args, REPO, 0.0) == 2
    out = capsys.readouterr()
    assert "no TPU" in out.err
    assert not any(line.startswith("{") for line in out.out.splitlines())


def test_step_bytes_come_from_the_configuration():
    cell = cells.resolve(REPO, BENCH, "grayscott-carpet-r9-batch2")
    cfg = cell.config
    prog = SimpleNamespace(live_cells=lambda r: len(
        cfg["fractal"]["positions"]) ** r)
    done = [SimpleNamespace(ok=True, r=9, steps=32)] * 3
    ctx = SimpleNamespace(
        trace={"step_s": 2.0}, requests=done, live_cells=prog.live_cells,
        channels=cfg["channels"], itemsize=4, peaks=PEAKS["TPU v5 lite"])
    want = 100 * 3 * 2 * 8 ** 9 * 2 * 4 * 32 / (2.0 * 819e9)
    assert cell.reader("step_hbm_share")(ctx) == pytest.approx(want)
    ctx.trace = None
    assert cell.reader("step_hbm_share")(ctx) is None


def test_closed_mix_repeats_one_size():
    mix = cells.load_json(REPO / "chipbench" / "traffic"
                          / "closed-c4-r17-s64.json")
    gens = loadgen.closed_requests(mix, 2 ** 31 + 1)
    assert len(gens) == 4
    first = [next(g) for g in gens for _ in range(3)]
    assert {(q.r, q.steps, q.snapshot_every) for q in first} == {(17, 64, 0)}
    assert len({q.seed for q in first}) == len(first)
    assert len({q.idx for q in first}) == len(first)
    again = [next(g) for g in loadgen.closed_requests(mix, 2 ** 31 + 1)
             for _ in range(3)]
    assert again == first


@pytest.mark.parametrize("key", ["r", "steps", "snapshot_every"])
def test_closed_mix_takes_one_value(key):
    mix = {"loop": "closed", "clients": 1, "r": {"5": 1}, "steps": {"2": 1},
           "snapshot_every": {"0": 1}, "deadline_s": 60.0}
    mix[key] = {"1": 0.5, "2": 0.5}
    with pytest.raises(ValueError, match=key):
        loadgen.closed_requests(mix, 3)


def _answers(n, clients):
    out = []
    for i in range(n):
        res = SimpleNamespace(status="ok", state=f"state{i}",
                              snapshots=[(1, "snap")])
        out.append(harness.Sent(loadgen.Planned(i, 5, 2, 0, i), i % clients,
                                result=_Result(**vars(res))))
    return out


@dataclasses.dataclass
class _Result:
    status: str
    state: object
    snapshots: list


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 9, 2 ** 40 + 3])
def test_sample_keeps_one_answer_per_client(seed):
    sample = harness.Sample(seed, 3)
    sent = _answers(20, 3)
    failed = harness.Sent(loadgen.Planned(99, 5, 2, 0, 1), 0,
                          result=_Result("failed", "partial", []))
    for s in sent[:10] + [failed] + sent[10:]:
        sample.offer(s)
    kept = sample.requests()
    assert sorted(s.client for s in kept) == [0, 1, 2]
    assert all(s.result.state is not None for s in kept)
    dropped = [s for s in sent + [failed] if s not in kept]
    assert all(s.result.state is None and not s.result.snapshots
               for s in dropped)
    assert all(s.result.status in ("ok", "failed") for s in sent)


def test_sample_draws_from_the_seed_alone():
    def pick(seed, order):
        sample = harness.Sample(seed, 2)
        sent = _answers(12, 2)
        for i in order:
            sample.offer(sent[i])
        return sorted(s.planned.idx for s in sample.requests())

    forward = list(range(12))
    # the two clients interleave differently; each client's own order
    # is the same
    other = [0, 2, 4, 6, 1, 8, 10, 3, 5, 7, 9, 11]
    assert pick(5, forward) == pick(5, other)
    picks = collections.Counter(i for s in range(600)
                                for i in pick(s, forward) if i % 2 == 0)
    assert set(picks) == {0, 2, 4, 6, 8, 10}
    assert min(picks.values()) > 600 / 6 * 0.6


def test_benchmark_names_and_limits():
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert BENCH["paths"] == ["chipbench"]
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    assert json.loads(json.dumps(BENCH)) == BENCH
    assert not math.isnan(BENCH["run_seconds"])
