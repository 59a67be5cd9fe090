"""The ``nemotron_h_lm`` family in the benchmark: a tiny configuration
of it goes through ``run.py --rehearse`` and ``faults.py`` with no file
of ``benchmark/`` edited (``nemotron_family/`` beside this file holds a
configuration, a traffic mix, limits and a BENCHMARK.json that lists
``ssm_state_roofline.serve``; family and reference are the benchmark's
own), its counts are a hand count, the kernel's roofline reader does
its arithmetic and is found by a harness that lists it, the committed
readings of the chip hold to the committed limits, and the
configuration and the cell say what they were asked to say."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness import HERE, Harness  # noqa: E402
from benchmark.metrics import ssm_state_cost, ssm_state_roofline  # noqa: E402
from incubator_mxnet_tpu import tracing  # noqa: E402

FAMILY = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "nemotron_family")
CELL = "nemotron-3-super-120b-a12b.serve-closed128-decode"
METRIC = "ssm_state_roofline.serve"


def _run(script, *argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join("benchmark", script), "--rehearse",
         FAMILY, "--workload", "tiny-nemotron.serve", *argv],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=280)


@pytest.mark.parametrize("trace", [0, 1])
def test_a_tiny_nemotron_configuration_goes_through_rehearse(trace):
    out = _run("run.py", "--seed", str(2 ** 31 + 39), "--seconds", "0.4",
               "--trace", str(trace))
    assert out.returncode == 2, out.stderr[-3000:]
    result = json.loads(out.stderr.strip().splitlines()[-1].split(
        "not a result: ", 1)[1])
    assert result["correct"], result["compared"]
    assert result["attempted"] >= 3 and result["failed"] == 0
    # bfloat16 program against the float32 reference: no gap beyond
    # the fp8 control's noise
    assert result["compared"]["far_gap_share"]["value"] < 0.05
    # a rehearsal has no device plane: the kernel's share reads nothing
    expected = {"engine_step_ms.serve"} if trace else {
        "setup_s", "serve_tok_per_s", "itl_p95_ms"}
    assert set(result["metrics"]) == expected
    assert sorted(os.listdir(FAMILY)) == [
        "BENCHMARK.json", "configs", "limits", "traffic"]


def test_faults_py_plants_the_familys_faults(tmp_path):
    """``faults.py`` finds the new reference's faults by name and reads
    a row for each beside the program's and the control's; the
    program's row is correct and ``calibrate.py --judge`` reads every
    row again as it was judged."""
    rows_file = tmp_path / "rows.jsonl"
    seed = 2 ** 31 + 57
    out = _run("faults.py", "--seeds", str(seed), "--controls", "1",
               "--fault-seeds", "1", "--seconds", "0.3", "--faults",
               "state_not_carried,conv_skipped", "--out", str(rows_file))
    assert out.returncode == 0, out.stderr[-3000:]
    rows = [json.loads(line) for line in open(rows_file)]
    judged = {r["who"]: r["correct"] for r in rows if "correct" in r}
    assert judged == {"program": True, "control_fp8": judged["control_fp8"],
                      "fault_state_not_carried": False,
                      "fault_conv_skipped": False}
    again = _run("calibrate.py", "--judge", str(rows_file))
    assert again.returncode == 0, again.stderr[-2000:]
    said = [json.loads(line) for line in again.stdout.splitlines()]
    assert {r["who"]: r["correct"] for r in said} == judged


def test_the_familys_counts_are_a_hand_count_at_the_tiny_size():
    h = Harness(FAMILY, os.path.join(FAMILY, "BENCHMARK.json"))
    cfg = h.cell("tiny-nemotron.serve").config
    fam = h.family(cfg)
    # MEM*E: a Mamba layer (in_proj 64 x 336, out_proj 128 x 64, conv
    # 192 x 4, the recurrence 2 x 16 x 8 x 16); an expert layer (router
    # 8 x 64, latent 64 x 24 both ways, shared 64 x 48 both ways, of 3
    # experts of 2 x 24 x 32 the 4 held of 8); attention (q and o 64 x
    # 64, k and v 64 x 32)
    mamba = 64 * 336 + 128 * 64 + 192 * 4 + 2 * 16 * 8 * 16
    moe = 8 * 64 + 2 * 64 * 24 + 2 * 64 * 48 + 2 * 24 * 32 * 3 * 4 // 8
    attention = 64 * 128 + 64 * 64
    layers = 2 * (2 * mamba + 2 * moe + attention)
    head, pair = 2 * 64 * 97, 2 * 2 * 4 * 16
    assert fam.decode_flops(cfg, 30) == layers + head + pair * 30
    assert fam.prefill_flops(cfg, 30) == layers * 30 + head \
        + pair * 30 * 31 // 2
    n = sum(int(np.prod(s)) for s, _ in fam.param_shapes(cfg).values())
    assert n == 2 * 97 * 64 + 64 + 5 * 64 + 2 * (
        336 * 64 + 192 * 4 + 192 + 3 * 16 + 128 + 64 * 128) + 2 * (
        8 * 64 + 8 + 2 * 24 * 64 + 2 * 4 * 32 * 24 + 2 * 48 * 64) \
        + 2 * 64 * 64 + 2 * 32 * 64


# ------------------------------------------------- the kernel's share

def _window(launches, kernel_s, slots=128, other=0.5):
    """A traced window: ``launches`` decode launches in the newest
    session, ``kernel_s`` device seconds of ``ssm_state_step`` calls,
    and an operation that is not the kernel."""
    tracing.reset_for_tests()
    tracing.trace_event("span_clock", session=1, perf_counter=0.0,
                        time_ns=0)
    for i in range(launches):
        tracing.trace_event("span", id=i + 1, parent=None,
                            name="serve_decode_dispatch", t0=i, t1=i + 0.001,
                            session=1)
    cfg = Harness().cell(CELL).config
    return {"trace": {"op_seconds": {
        "%ssm_state_step.3 = f32[] custom-call(), "
        'custom_call_target="tpu_custom_call"': kernel_s,
        "%fusion.1 = f32[] fusion(), kind=kLoop": other}},
        "config": cfg, "traffic": {"engine": {"max_batch": slots}},
        "peaks": {"flops_per_s": {"bfloat16": 197e12},
                  "hbm_bytes_per_s": 819e9}}


@pytest.fixture
def spec():
    with open(os.path.join(HERE, "metrics", METRIC + ".json")) as f:
        yield json.load(f)
    tracing.reset_for_tests()


def test_the_kernels_share_is_the_bytes_over_its_time(spec):
    """Ten launches of the cell's 5 Mamba layers at 128 slots: 50 calls
    of 1,083,244,544 bytes (the states read and written, 4 MiB a slot,
    and the step's rows), 66.1 ms at 819 GB/s, over 80 ms of the
    kernel."""
    cfg = Harness().cell(CELL).config
    one = ssm_state_cost.state_step_bytes(cfg, 128)
    assert one == 4 * 128 * (2 * 128 * 64 * 128 + 2 * 8192 + 128
                             + 2 * 8 * 128) == 1083244544
    ctx = _window(10, 0.080)
    share = ssm_state_roofline.read(ctx, spec)
    assert share == pytest.approx(100 * 50 * one / 819e9 / 0.080)
    assert ctx["notes"][METRIC]["bound_by"] == "bytes"
    assert ctx["notes"][METRIC]["calls"] == 50


def test_a_share_over_105_raises_and_nothing_reads_none(spec):
    with pytest.raises(RuntimeError, match="counted too high"):
        ssm_state_roofline.read(_window(10, 0.050), spec)
    assert ssm_state_roofline.read(_window(10, 0.0), spec) is None
    assert ssm_state_roofline.read(_window(0, 0.08), spec) is None
    assert ssm_state_roofline.read({"trace": None}, spec) is None


def test_the_kernels_share_is_found_by_a_harness(tmp_path, spec):
    """As the benchmark would list it: the files alone in a directory
    of their own, with a BENCHMARK.json whose ``per_layer`` names it
    alone, for the new cell alone."""
    d = tmp_path / "bench"
    (d / "metrics").mkdir(parents=True)
    for name in (METRIC + ".json", "ssm_state_roofline.py",
                 "ssm_state_cost.py", "program_span.py"):
        shutil.copy(os.path.join(HERE, "metrics", name), d / "metrics")
    (d / "metrics" / "__init__.py").write_text("")
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    bench["per_layer"] = [
        {"name": METRIC, "unit": "%", "better": "higher",
         "source": "device_trace", "layer": "kernels",
         "moves": "serve_tok_per_s", "workloads": [CELL]}]
    (d / "BENCHMARK.json").write_text(json.dumps(bench))
    h = Harness(str(d), str(d / "BENCHMARK.json"))
    got = h.read_per_layer(CELL, dict(_window(10, 0.080), chips=1))
    assert got[METRIC]["value"] == pytest.approx(
        100 * 50 * 1083244544 / 819e9 / 0.080)
    other = h.read_per_layer("xing4.0-29b-a4b.serve-closed64-decode",
                             dict(_window(10, 0.080), chips=1))
    assert METRIC not in other


# --------------------------------------- the configuration and the cell

CUT = {"num_hidden_layers": (88, 11),
       "hybrid_override_pattern": (
           "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
           "EMEMEMEMEM*EMEMEMEM*EMEMEMEME", "MEMEMEM*EME"),
       "n_routed_experts": (512, 128), "vocab_size": (131072, 32768)}
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 4096,
    "intermediate_size": 2688, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu",
    "mamba_num_heads": 128, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 2688, "moe_latent_size": 1024,
    "moe_shared_expert_intermediate_size": 5376,
    "moe_shared_expert_overlap": False,
    "mtp_hybrid_override_pattern": "*E", "n_group": 1, "n_groups": 8,
    "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 22,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
    "rescale_prenorm_residual": True, "residual_in_fp32": False,
    "rope_theta": 10000, "routed_scaling_factor": 5,
    "sliding_window": None, "ssm_state_size": 128,
    "tie_word_embeddings": False, "time_step_floor": 0.0001,
    "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
    "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True}


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_the_configuration_holds_the_published_value(key):
    assert Harness().cell(CELL).config[key] == PUBLISHED[key]


def test_what_the_configuration_cut_is_what_it_says():
    """Depth (one period of the pattern), the experts one chip holds,
    the vocabulary's slice: nothing else differs from the published
    file, each is listed with what was published; 4,648,163,712
    parameters."""
    h = Harness()
    cfg = h.cell(CELL).config
    entry, = (c for c in h.spec["configs"]
              if c["name"] == "nemotron-3-super-120b-a12b")
    assert entry["reduced"] == list(CUT)
    assert {k: cfg[k] for k in CUT} == {k: v[1] for k, v in CUT.items()}
    assert cfg["published"] == {k: v[0] for k, v in CUT.items()}
    assert CUT["hybrid_override_pattern"][0].startswith(
        cfg["hybrid_override_pattern"])
    assert set(cfg["reduced_why"]) == set(entry["reduced"])
    dep = cfg["deployment"]
    assert dep["ep_size"] * cfg["n_routed_experts"] \
        == cfg["published"]["n_routed_experts"]
    assert dep["vocab_split"] * cfg["vocab_size"] \
        == cfg["published"]["vocab_size"]
    assert set(cfg["assumed"]) >= {
        "attention_positions", "multi_token_prediction",
        "latent_moe_inputs", "state_dtypes", "weights"}
    joyai = h.cell("joyai-llm-flash.serve-closed64-decode").config
    assert cfg["serve"] == joyai["serve"] and "train" not in cfg
    fam = h.family(cfg)
    n = sum(int(np.prod(s)) for s, _ in fam.param_shapes(cfg).values())
    assert n == 5 * 109640064 + 5 * (54530560 + 128 * 5505024) \
        + 35655680 + 268439552 == 4648163712


def test_the_cell_is_the_one_named():
    h = Harness()
    cell = h.cell(CELL)
    entry, = (w for w in h.spec["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "nemotron-3-super-120b-a12b", "serve-closed128-decode", 1)
    assert len(entry["why"]) <= 200
    t = cell.traffic
    assert (t["clients"], t["checked_requests"], t["trace_seconds"]) \
        == (128, 32, 3.0)
    assert t["engine"] == {"max_batch": 128, "max_len": 8192,
                           "block_size": 16, "num_blocks": 128 * 305 + 1}
    other = h.cell("joyai-llm-flash.serve-closed64-decode").traffic
    assert (t["prompt_len"], t["new_tokens"]) == (other["prompt_len"],
                                                  other["new_tokens"])
    # throughput and set-up, not the tail (PERF.md, section 7)
    for metric in h.spec["end_to_end"]:
        listed = CELL in metric.get("workloads", [CELL])
        assert listed == (metric["name"] in ("setup_s", "serve_tok_per_s"))
    assert sorted(m["name"] for m in h.metrics(CELL, "per_layer")) \
        == sorted(["step_mfu.serve", "device_idle.serve",
                   "engine_host_ms.serve", "decode_ms.serve"])


def test_the_committed_readings_hold_to_the_limits():
    """readings/<cell>.jsonl keeps the chip's rows (benchmark/faults.py
    and run.py): under the committed limits every program row is
    correct and every control and planted fault is not."""
    path = os.path.join(HERE, "readings", CELL + ".jsonl")
    out = subprocess.run(
        [sys.executable, os.path.join("benchmark", "calibrate.py"),
         "--workload", CELL, "--judge", path],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    said = [json.loads(line) for line in out.stdout.splitlines()]
    program = [r["correct"] for r in said if r["who"] == "program"]
    others = [r["correct"] for r in said if r["who"] != "program"]
    assert len(program) >= 3 and all(program)
    faults = {r["who"] for r in said if r["who"].startswith("fault_")}
    from benchmark.reference.nemotron_h import FAULTS
    assert faults == {f"fault_{f}" for f in FAULTS}
    assert others and not any(others)
