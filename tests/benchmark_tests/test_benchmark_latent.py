"""The ``latent_moe_lm`` family in the benchmark: a tiny configuration
of it goes through ``run.py --rehearse`` and through ``faults.py`` with
no file of ``benchmark/`` edited (``latent_family/`` beside this file
holds a configuration, a traffic mix, limits and a BENCHMARK.json;
family and reference are the benchmark's own), and the configuration
and the cell that ISSUE 28 added say what they were asked to say."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness import HERE, Harness  # noqa: E402

LATENT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "latent_family")
CELL = "joyai-llm-flash.serve-closed64-decode"


def _last_json(text, after):
    return json.loads(text.strip().splitlines()[-1].split(after, 1)[1])


@pytest.mark.parametrize("trace", [0, 1])
def test_a_tiny_latent_configuration_goes_through_rehearse(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join("benchmark", "run.py"),
         "--rehearse", LATENT, "--workload", "tiny-latent.serve",
         "--seed", str(2 ** 31 + 27), "--seconds", "0.4",
         "--trace", str(trace)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=280)
    assert out.returncode == 2, out.stderr[-3000:]
    result = _last_json(out.stderr, "not a result: ")
    assert result["correct"], result["compared"]
    assert result["attempted"] >= 3 and result["failed"] == 0
    # bfloat16 program against the float32 reference: no gap beyond
    # the fp8 control's noise
    assert result["compared"]["far_gap_share"]["value"] < 0.05
    # the CPU has no device plane: the readers of the program's spans
    # find no traced window and are left out
    expected = {"engine_step_ms.serve"} if trace else {
        "setup_s", "serve_tok_per_s", "itl_p95_ms"}
    assert set(result["metrics"]) == expected
    # found among the benchmark's own files, by the name in the toy
    # configuration: nothing of the family lies in the toy directory
    assert sorted(os.listdir(LATENT)) == [
        "BENCHMARK.json", "configs", "limits", "traffic"]


def test_planted_faults_are_read_like_the_control(tmp_path):
    """``faults.py`` on the toy cell: the program's row is correct,
    the control's and the routed layer's coarse faults are not, the
    sound reference in bfloat16 put in the program's place (a witness)
    is, and every row is one ``calibrate.py --judge`` can read
    again."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    rows_file = tmp_path / "rows.jsonl"
    out = subprocess.run(
        [sys.executable, os.path.join("benchmark", "faults.py"),
         "--rehearse", LATENT, "--workload", "tiny-latent.serve",
         "--seeds", f"{2 ** 31 + 51},5", "--controls", "1",
         "--fault-seeds", "1", "--seconds", "0.3",
         "--faults", "top7,no_routed_experts", "--witness", "bf16",
         "--out", str(rows_file), "--positions", str(tmp_path)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=280)
    assert out.returncode == 0, out.stderr[-3000:]
    rows = [json.loads(line) for line in open(rows_file)]
    judged = {(r["who"], r["seed"]): r["correct"] for r in rows
              if "correct" in r}
    assert judged == {
        ("program", 2 ** 31 + 51): True, ("program", 5): True,
        ("control_fp8", 2 ** 31 + 51): False,
        ("fault_top7", 2 ** 31 + 51): False,
        ("fault_no_routed_experts", 2 ** 31 + 51): False,
        ("witness_bf16", 2 ** 31 + 51): True,
        ("witness_bf16", 5): True}
    assert {p.name for p in tmp_path.glob("*.npz")} == {
        "positions_5.npz", f"positions_{2 ** 31 + 51}.npz"}
    again = subprocess.run(
        [sys.executable, os.path.join("benchmark", "calibrate.py"),
         "--rehearse", LATENT, "--workload", "tiny-latent.serve",
         "--judge", str(rows_file)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert again.returncode == 0, again.stderr[-2000:]
    said = [json.loads(line) for line in again.stdout.splitlines()]
    assert {(r["who"], r["seed"]): r["correct"] for r in said} == judged


PUBLISHED = {
    "hidden_size": 2048, "num_attention_heads": 32,
    "num_key_value_heads": 32, "q_lora_rank": 1536,
    "kv_lora_rank": 512, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "qk_head_dim": 192, "v_head_dim": 128,
    "head_dim": 64, "moe_intermediate_size": 768,
    "intermediate_size": 7168, "n_routed_experts": 256,
    "num_experts_per_tok": 8, "n_shared_experts": 1,
    "first_k_dense_replace": 1, "moe_layer_freq": 1, "n_group": 1,
    "topk_group": 1, "ep_size": 1, "max_position_embeddings": 131072,
    "rms_norm_eps": 1e-06, "rope_theta": 32000000,
    "routed_scaling_factor": 2.5, "num_nextn_predict_layers": 1,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "hidden_act": "silu", "model_type": "joyai_llm_flash",
    "norm_topk_prob": True, "rope_interleave": True,
    "rope_scaling": None, "attention_bias": False,
    "tie_word_embeddings": False}


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_the_configuration_holds_the_published_value(key):
    cfg = Harness().cell(CELL).config
    assert cfg[key] == PUBLISHED[key]


def test_what_the_configuration_cut_is_what_it_says():
    """Depth, and the vocabulary's slice that the harness's own
    comparison forces (PERF.md, section 7): nothing else differs from
    the published file, and both are listed with what was
    published."""
    h = Harness()
    cfg = h.cell(CELL).config
    entry, = (c for c in h.spec["configs"]
              if c["name"] == "joyai-llm-flash")
    assert entry["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["vocab_size"]) == (5, 32320)
    assert cfg["published"] == {"num_hidden_layers": 40,
                                "vocab_size": 129280}
    assert set(cfg["reduced_why"]) == set(entry["reduced"])
    assert cfg["vocab_size"] * 4 == cfg["published"]["vocab_size"]
    # the deployment it states is one in which chips do share what is
    # sliced: no decoder layer, but embedding and head, four ways
    assert cfg["deployment"]["chips_sharing_a_layer"] == 1
    assert cfg["deployment"]["chips_sharing_embedding_and_head"] \
        == cfg["deployment"]["vocab_split"] == 4
    assert cfg["deployment"]["ep_size"] == cfg["ep_size"] == 1
    # the README's eight keys of a served configuration, no other
    assert sorted(cfg["serve"]) == sorted([
        "weights_dtype", "kv_dtype", "matmul_precision", "decoding",
        "reference_precision", "control_precision", "far_gap_sigmas",
        "yardstick_flips"])
    assert "train" not in cfg
    # 5,160,993,792 parameters: the leaves the family lists
    n = 0
    for shape, _ in h.family(cfg).param_shapes(cfg).values():
        size = 1
        for d in shape:
            size *= d
        n += size
    assert n == 5160993792


def test_the_cell_is_the_one_the_issue_named():
    h = Harness()
    cell = h.cell(CELL)
    t = cell.traffic
    assert (t["kind"], t["clients"]) == ("serve", 64)
    assert t["prompt_len"] == {"dist": "loguniform", "min": 512,
                               "max": 4096}
    assert t["new_tokens"] == {"dist": "uniform", "min": 256,
                               "max": 768}
    # as many sizes as callers: with fewer, callers that hold the same
    # size stay in step for ever (the traffic file's pool_why)
    assert t["pool_requests"] == t["clients"] == 64
    assert t["checked_requests"] == 32
    eng = t["engine"]
    assert (eng["max_batch"], eng["max_len"]) == (64, 8192)
    # never preempted: every slot's longest request, and the scratch
    longest = t["prompt_len"]["max"] + t["new_tokens"]["max"]
    assert eng["num_blocks"] > 64 * -(-longest // eng["block_size"])
    entry, = (w for w in h.spec["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    # throughput, not the tail: a window of this load takes some 40
    # prompts in, a tenth of its steps hold a prefill whose cost
    # follows the prompt's length, and the 95th gap is set by the 17
    # longest: it spread by 4.8% over six seeds and by 19.5% over one
    # run's windows where 3% is admitted (PERF.md, sections 6 and 7)
    for metric in h.spec["end_to_end"]:
        listed = CELL in metric.get("workloads", [CELL])
        assert listed == (metric["name"] in (
            "setup_s", "serve_tok_per_s"))
    # no per-layer metric came with it: the serve metrics without a
    # list of cells that move what the cell reports are the new
    # cell's by themselves
    own = json.load(open(os.path.join(HERE, "testdata", "rehearse",
                                      "BENCHMARK.json")))
    assert [m["name"] for m in h.spec["per_layer"]] == \
        [m["name"] for m in own["per_layer"]]
    assert sorted(m["name"] for m in h.metrics(CELL, "per_layer")) \
        == sorted(["step_mfu.serve", "device_idle.serve",
                   "engine_host_ms.serve", "decode_ms.serve"])


@pytest.mark.parametrize("lo,hi", [(0.0, 2.0), (1.5, 4.0), (3.0, 5.0)])
def test_a_slice_of_the_long_run_counts_as_the_window_does(lo, hi):
    """``ramp.py`` cuts windows out of one long run: each reports
    what ``serve.Drive.end_to_end`` would have, had the window been
    opened and closed there."""
    import numpy as np
    from benchmark import ramp, serve
    rs = np.random.RandomState(7)
    requests = []
    for _ in range(12):
        due = rs.uniform(0, 4)
        requests.append((due, 10, list(due + np.cumsum(
            rs.uniform(0.02, 0.2, rs.randint(2, 40))))))
    drive = serve.Drive.__new__(serve.Drive)
    drive.t0, drive.t_close = lo, hi
    drive.records = {i: {"due": due, "times": times}
                     for i, (due, _, times) in enumerate(requests)}
    theirs = drive.end_to_end()
    mine = ramp.windows(requests, lo, hi)
    assert mine["serve_tok_per_s"] == pytest.approx(
        theirs["serve_tok_per_s"])
    assert mine["itl_p95_ms"] == pytest.approx(theirs["itl_p95_ms"])
    assert mine["taken_in"] == sum(
        1 for _, _, t in requests if lo < t[0] <= hi)
