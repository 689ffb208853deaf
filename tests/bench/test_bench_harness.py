"""The chip benchmark's own pieces, on the CPU: manifest rules, the
operation and byte counts, the trace reduction, the per-layer readout,
and the refusal to run without a TPU."""
import copy
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]


@pytest.fixture(scope="module")
def bench():
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    import bench.harness as harness
    return harness


@pytest.fixture(scope="module")
def manifest():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


# -- manifest ---------------------------------------------------------------

def test_manifest_is_valid(bench, manifest):
    assert bench.validate_manifest(manifest) == []


@pytest.mark.parametrize("bad", ["has space", "a/b", "ü", "", "x" * 65,
                                 "comma,name"])
def test_manifest_rejects_bad_names(bench, manifest, bad):
    m = copy.deepcopy(manifest)
    m["per_layer"][0]["name"] = bad
    assert bench.validate_manifest(m)


@pytest.mark.parametrize("unit", ["tokens per second", "µs", "", "x" * 17])
def test_manifest_rejects_bad_units(bench, manifest, unit):
    m = copy.deepcopy(manifest)
    m["end_to_end"][1]["unit"] = unit
    assert any("unit" in e for e in bench.validate_manifest(m))


def test_manifest_moves_target_must_be_reported(bench, manifest):
    m = copy.deepcopy(manifest)
    m["workloads"].append(dict(m["workloads"][0], name="other.cell"))
    fleet_metric = next(p for p in m["per_layer"]
                        if p["moves"] == "round_s")
    fleet_metric["workloads"] = [w["name"] for w in m["workloads"]]
    errs = bench.validate_manifest(m)
    assert any("other.cell does not report round_s" in e for e in errs)


def test_every_cell_reports_setup_another_metric_and_a_layer(bench,
                                                             manifest):
    man = bench.Manifest(manifest)
    for cell in manifest["workloads"]:
        names = {m["name"] for m in man.end_to_end(cell["name"])}
        assert "setup_s" in names and len(names) >= 2
        assert man.per_layer(cell["name"])


def test_every_file_the_manifest_names_exists(manifest):
    for c in manifest["configs"]:
        assert (ROOT / c["file"]).is_file()
    for w in manifest["workloads"]:
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
        assert (ROOT / "bench" / "limits" / f"{w['name']}.json").is_file()
    for m in manifest["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()


def test_layers_are_named_in_perf_md(manifest):
    perf = (ROOT / "PERF.md").read_text()
    for m in manifest["per_layer"]:
        assert f"`{m['layer']}`" in perf, m["layer"]


# -- work -------------------------------------------------------------------

@pytest.mark.parametrize("M,K,N,r", [(240, 768, 768, 8), (768, 768, 3072, 32),
                                     (5760, 3072, 768, 2)])
def test_lora_kernel_work_matches_hand_count(bench, M, K, N, r):
    from bench.work import lora_matmul as lm
    one = lm.kernel_call(M, K, N, r)
    assert one["flops"] == 2 * M * K * N + 2 * M * r * N
    assert one["bytes"] == 4 * (M * K + K * N + M * r + r * N + M * N)
    whole = lm.lora_linear_intensity(M, K, N, r)
    # the kernel plus t = x·A outside it is the whole linear
    assert one["flops"] + 2 * M * K * r == whole["flops"]
    assert one["bytes"] + 4 * (M * K + K * r + M * r) == whole[
        "fused_bytes"]
    lanes = lm.kernel_call(M, K, N, r, lanes=24)
    assert lanes["flops"] == 24 * one["flops"]
    assert lanes["bytes"] == 4 * 24 * (M * K + M * r + r * N + M * N) \
        + 4 * K * N


def test_lora_kernel_work_from_trace_shapes(bench):
    from bench import roofline
    from bench.work import lora_matmul as lm
    hlo = ("%lora_matmul.268 = f32[24,768,3072]{2,1,0:T(8,128)} custom-call("
           "f32[24,768,1024]{2,1,0:T(8,128)} %a, f32[1024,3072]{1,0} %b, "
           "f32[24,768,32]{2,1,0} %c, f32[24,32,3072]{2,1,0} %d, "
           "f32[1,32]{1,0} %e, f32[1,1]{1,0:S(6)} %f)")
    out, ops = roofline.shapes(hlo)
    assert out == (24, 768, 3072) and ops[0] == (24, 768, 1024)
    assert lm.from_operands(ops, out) == lm.kernel_call(768, 1024, 3072, 32,
                                                        lanes=24)


def test_flash_kernel_work_matches_hand_count(bench):
    from bench.work import flash_attention as fa
    w = fa.from_operands([(24, 32, 12, 24, 64)] * 3, None)
    assert w["flops"] == 24 * 32 * 4 * 12 * 24 * 24 * 64
    assert w["bytes"] == 4 * 24 * 32 * (2 * 12 * 24 * 64 + 2 * 12 * 24 * 64)


def _vit():
    return json.loads((ROOT / "bench" / "configs" /
                       "vit-base-paper.json").read_text())["model"]


def test_round_work_matches_hand_counts(bench):
    from bench.work import fleet_round
    vit = _vit()
    assert fleet_round.block_params(vit) == 12 * (4 * 768 ** 2
                                                  + 2 * 768 * 3072)
    assert fleet_round.adapter_width(vit) == 12 * (8 * 768 + 2 * (768
                                                                  + 3072))
    # the paper fleet's round: about 3e13 operations (issue's count)
    r = fleet_round.round_flops(vit, seq=24, batch=10, trained_steps=216,
                                trained_rank_steps=216 * 2.0,
                                local_eval_seqs=72 * 32,
                                local_eval_rank_seqs=72 * 32 * 2.0,
                                global_eval_seqs=720, max_rank=32)
    assert 2.5e13 < r < 3.5e13


@pytest.mark.parametrize("cells", [1, 5])
def test_shares_stay_under_100_percent_at_peak_speed(bench, cells):
    """A kernel that ran exactly at its least time reads 100 %, never
    more; one that ran slower reads less."""
    from bench import roofline
    from bench.trace import Event
    from bench.work import lora_matmul as lm
    peaks = bench.load_json(ROOT / "bench" / "peaks.json")["TPU v5 lite"]
    hlo = ("%lora_matmul.1 = f32[24,256,3072]{2,1,0} custom-call("
           "f32[24,256,1024]{2,1,0}, f32[1024,3072]{1,0}, f32[24,256,32]"
           "{2,1,0}, f32[24,32,3072]{2,1,0}, f32[1,32]{1,0}, f32[1,1]{1,0})")
    out, ops = roofline.shapes(hlo)
    w = lm.from_operands(ops, out)
    least = max(w["flops"] / peaks["bf16_flops_per_s"],
                w["bytes"] / peaks["hbm_bytes_per_s"])
    ev = [Event(hlo, 0, int(least * 1e9 * cells) + 1)]
    s = roofline.share(ev, lm.from_operands, peaks)
    assert s["share"] <= 100.0
    assert s["share"] == pytest.approx(100.0 / cells, rel=1e-3)


# -- trace reduction --------------------------------------------------------

def _events(spec):
    from bench.trace import Event
    return [Event(n, a, b) for n, a, b in spec]


def test_busy_is_the_union_of_nested_and_overlapping_ops(bench):
    from bench.trace import Reduced
    ops = _events([("%while.1 = x", 0, 100), ("%fusion.1 = x", 10, 30),
                   ("%fusion.2 = x", 40, 60), ("%copy.1 = x", 150, 170),
                   ("%copy.2 = x", 160, 200)])
    spans = _events([("bench.round", 0, 300), ("bench.stage_round", 100,
                                                150),
                     ("bench.record", 200, 300)])
    r = Reduced(window=(0, 300), devices=[{"XLA Ops": ops}], spans=spans)
    assert r.busy() == [(0, 100), (150, 200)]
    assert r.busy_s == pytest.approx(150e-9)
    assert r.idle_share() == pytest.approx(0.5)
    gaps = r.gaps()
    assert gaps == [("stage_round", pytest.approx(50e-9)),
                    ("record", pytest.approx(100e-9))]
    secs = r.op_seconds()
    # self time: the loop less its body
    assert secs["while"] == pytest.approx(60e-9)
    assert secs["fusion"] == pytest.approx(40e-9)
    # overlapping events that do not nest each keep their own time
    assert secs["copy"] == pytest.approx(60e-9)
    bd = r.breakdown()
    assert bd["idle_gaps"][0][0] == "record"
    assert len(bd["device_ops"]) <= 10


def test_kernel_time_by_name(bench):
    from bench.trace import Reduced, short_name
    ops = _events([("%lora_matmul.3 = f32[2]{0} custom-call()", 0, 40),
                   ("%_fa_jit.7 = f32[2]{0} custom-call()", 40, 50),
                   ("%lora_matmul.9 = f32[2]{0} custom-call()", 60, 80)])
    r = Reduced(window=(0, 100), devices=[{"XLA Ops": ops}],
                spans=_events([("bench.round", 0, 100)]))
    lora = r.events_matching(lambda e: short_name(e.name) == "lora_matmul")
    assert sum(e.dur for e in lora) == 60
    assert r.op_seconds()["_fa_jit"] == pytest.approx(10e-9)


def test_recorded_trace_reduces(bench):
    """A trace recorded on a TPU v5e (tests/bench/data, written by
    record_trace.py there): a warm-up, a kernel call and a host-only sleep
    inside harness spans."""
    from bench.trace import reduce_file, short_name
    path = ROOT / "tests" / "bench" / "data" / "small.xplane.pb"
    meta = json.loads((path.parent / "small.json").read_text())
    r = reduce_file(str(path))
    assert r.window_s == pytest.approx(meta["window_s"], rel=0.05)
    assert 0.0 < r.busy_s < r.window_s
    idle = r.idle_share()
    assert meta["sleep_s"] / r.window_s * 0.9 < idle < 1.0
    by_span = {}
    for name, sec in r.gaps():
        by_span[name] = by_span.get(name, 0.0) + sec
    assert by_span["sleep"] == pytest.approx(meta["sleep_s"], rel=0.1)
    names = {short_name(e.name) for e in r.ops()}
    assert meta["kernel"] in names


# -- per-layer readout ------------------------------------------------------

def _readout(bench, monkeypatch, silent):
    """The cell's per-layer line from stub readers that each read 1.0,
    except ``silent``, which reads nothing."""
    import types
    man = bench.Manifest(json.loads((ROOT / "BENCHMARK.json").read_text()))
    cell = man.raw["workloads"][0]

    def load_module(path, name=None):
        stem = Path(path).stem
        return types.SimpleNamespace(
            read=lambda ro: None if stem == silent else 1.0)
    monkeypatch.setattr(bench, "load_module", load_module)
    run = types.SimpleNamespace(
        cell=cell, devices=[types.SimpleNamespace(device_kind="TPU v5 lite")])
    return bench.per_layer_metrics(run, man, None, None)


@pytest.mark.parametrize("silent", [
    m["name"] for m in json.loads((ROOT / "BENCHMARK.json").read_text())[
        "per_layer"]])
def test_a_metric_that_reads_nothing(bench, monkeypatch, silent):
    """A kernel's roofline whose kernel ran nothing is left out of the
    line; any other per-layer metric that reads nothing ends the run."""
    if silent.endswith("_roofline"):
        line = _readout(bench, monkeypatch, silent)
        assert silent not in line and len(line) >= 1
        assert all(v["value"] == 1.0 for v in line.values())
    else:
        with pytest.raises(bench.BenchError):
            _readout(bench, monkeypatch, silent)


# -- refusal without a TPU --------------------------------------------------

@pytest.mark.parametrize("cell", ["fleet.vit-base.paper"])
def test_run_refuses_without_a_tpu(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", cell,
         "--seed", str(2 ** 31 + 3), "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=str(ROOT), timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
