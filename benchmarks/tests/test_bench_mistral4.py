"""What PR 48 added to the yardstick, pinned on the CPU: the Mistral-4
configuration and traffic files, ``lib/flops_mistral4.py``'s arithmetic, the
new reader ``readers/mfu_prefill.py`` on a hand-made trace of two prefills
whose MFU is known, and the five new metric files on hand-built spans.  Pure
functions and files: no device, no timing.
"""

import importlib
import json
import os
import types

import pytest

from benchmarks.lib import flops, flops_mistral4 as fl
from benchmarks.lib import host_spans as hs
from benchmarks.lib import trace_reduce as tr
from benchmarks.lib import traffic

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CELL = "mistral4_ep8_longdoc_closed32"
# What may differ from the source: the cuts, and nothing that is a width.
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
NEW_METRICS = ["latent_long_decode_roofline.serve", "prefill_mfu.serve",
               "prefill_useful_pct.serve_rate", "ep8_expert_tokens.serve",
               "ep8_experts_touched_pct.serve"]


def load(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return load(HERE, "configs", "mistral_small4_l9_ep8.json")


@pytest.fixture(scope="module")
def mix():
    return load(HERE, "traffic", "longdoc_closed32.json")


def test_the_configuration_is_the_source_but_for_its_three_cuts(config):
    published = config["published"]
    assert config["reduced"] == REDUCED
    assert {k for k in published if config[k] != published[k]} == set(REDUCED)
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (9, 16, 16384)
    assert config["vocab_size"] * 8 == published["vocab_size"]
    assert config["num_hidden_layers"] * 4 == published["num_hidden_layers"]
    for key in ("assumed", "deployment", "memory", "reduced_why"):
        assert config[key]
    assert "32 chips" in config["deployment"]
    assert "4 pipeline stages" in config["deployment"]
    assert "vision" in config["assumed"]
    # The program's config at the published widths, key by key.
    m, rope = config["model"], published["rope_parameters"]
    same = {"d_model": "hidden_size", "n_head": "num_attention_heads",
            "q_lora_rank": "q_lora_rank", "kv_lora_rank": "kv_lora_rank",
            "qk_nope_head_dim": "qk_nope_head_dim",
            "qk_rope_head_dim": "qk_rope_head_dim",
            "v_head_dim": "v_head_dim", "d_expert": "moe_intermediate_size",
            "n_routed_experts": "n_routed_experts",
            "top_k": "num_experts_per_tok",
            "routed_scaling_factor": "routed_scaling_factor",
            "rms_eps": "rms_norm_eps"}
    assert {k: m[k] for k in same} == {k: published[v]
                                       for k, v in same.items()}
    same = {"rope_theta": "rope_theta", "rope_factor": "factor",
            "rope_original_max": "original_max_position_embeddings",
            "rope_beta_fast": "beta_fast", "rope_beta_slow": "beta_slow",
            "rope_mscale": "mscale", "rope_mscale_all_dim": "mscale_all_dim",
            "query_scale_beta": "llama_4_scaling_beta"}
    assert {k: m[k] for k in same} == {k: rope[v] for k, v in same.items()}
    assert published["qk_head_dim"] == (
        m["qk_nope_head_dim"] + m["qk_rope_head_dim"])
    assert (published["first_k_dense_replace"], published["n_shared_experts"],
            published["rope_interleave"], published["norm_topk_prob"]) == (
                0, 1, True, True)
    assert (m["n_layer"], m["experts_held"], m["vocab_size"]) == (9, 16, 16384)
    assert config["engine"] == {"max_batch_size": 32, "max_seq_len": 16384}
    fam = importlib.import_module("benchmarks.families." + config["family"])
    for name in ("model", "tiny"):
        cfg = fam.config(config[name])
        assert cfg.experts_held * 2 <= cfg.n_routed_experts
        assert cfg.latent_dim == cfg.kv_lora_rank + cfg.qk_rope_head_dim
    # the rehearsal's 64 + 3 positions cross the tiny trained length four
    # times; the chip's two-layer check stays below the published one
    assert config["tiny"]["rope_original_max"] * 4 <= 64 < (
        m["rope_original_max"])
    bench = load(ROOT, "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == config["name"])
    assert entry["reduced"] == REDUCED and entry["source"] == config["source"]
    assert entry["file"] == "benchmarks/configs/mistral_small4_l9_ep8.json"


def test_the_traffic_fits_the_cell_and_falls_on_the_two_long_rungs(config,
                                                                   mix):
    sizes = traffic.sizes(mix)
    eng = config["engine"]
    assert len(sizes) == mix["arrivals"]["clients"] == eng["max_batch_size"]
    assert max(p + o for p, o in sizes) == 15769 < eng["max_seq_len"] - 1
    assert (mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
            < eng["max_seq_len"] - 1)
    assert (mix["prompt_tokens"], mix["output_tokens"]) == (
        {"dist": "lognormal", "median": 8192, "sigma": 0.5, "min": 2048,
         "max": 15360},
        {"dist": "lognormal", "median": 256, "sigma": 0.5, "min": 64,
         "max": 768})
    rungs = [sum(1 for p, _ in sizes if lo < p <= hi) for lo, hi in (
        (0, 4096), (4096, 8192), (8192, 16384))]
    assert rungs == [0, 17, 15]
    # half the prompts end beyond the trained 8192 positions, where the
    # query scale is not 1, and every stream reads thousands of latents
    trained = config["model"]["rope_original_max"]
    assert sum(p > trained for p, _ in sizes) == 15
    assert min(p for p, _ in sizes) == 4745
    assert 9000 < flops.mean_decode_context(sizes) < 9050
    tiny = dict(mix, **mix["tiny"])
    assert max(p + o for p, o in traffic.sizes(tiny)) < (
        config["tiny_engine"]["max_seq_len"] - 1)
    a, b = traffic.requests(mix, 4800000019), traffic.requests(mix, 7)
    assert a != b and sorted(r["prompt_tokens"] for r in a) == sorted(
        r["prompt_tokens"] for r in b)


def test_the_parameter_count_is_the_published_models(config):
    """ISSUE 48's count: attention 28.05 M, one expert (routed or shared)
    25.17 M, a router 0.52 M, 53.7 M a layer outside its routed experts; the
    whole model 119.0 B, 6.6 B active (the published 119B-A6.5B)."""
    pub = dict(config["model"], n_layer=36, experts_held=128,
               vocab_size=config["published"]["vocab_size"])
    assert fl.mla_params(pub) == (
        4096 * 1024 + 1024 * 32 * 128 + 4096 * 320 + 256 * 32 * 192
        + 32 * 128 * 4096)
    assert round(fl.mla_params(pub) / 1e6, 2) == 28.05
    assert fl.expert_params(pub) == 3 * 4096 * 2048 == 25165824
    assert round(fl.router_params(pub) / 1e6, 2) == 0.52
    assert round(fl.nonexpert_layer_params(pub) / 1e6, 1) == 53.7
    assert round(fl.model_params(pub, 128) / 1e9, 1) == 119.0
    assert round(fl.model_params(pub, 4) / 1e9, 1) == 6.6


def test_a_steps_bytes_and_a_prefills_operations_from_the_cells_shapes(
    config
):
    m = config["model"]
    assert fl.held_expert_slots(m) == 144
    # 9 attentions + 9 shared experts in bf16, nine float32 routers, the
    # head: 1.11 GB (ISSUE 48's 0.98 + 0.13)
    assert round(fl.nonexpert_weight_bytes(m) / 1e9, 2) == 1.11
    assert fl.latent_bytes_per_position(m) == 9 * 320 * 2 == 5760
    step = fl.decode_step_bytes(m, {"experts_touched": 92.0}, 28, 8500.0)
    assert step == pytest.approx(
        fl.nonexpert_weight_bytes(m) + 92 * 2 * 25165824 + 28 * 8500 * 5760)
    assert 8.5 < step / 819e9 * 1e3 < 9.0  # ms at the v5e's bandwidth
    # idle: no slot, no expert: the weights outside the experts alone
    assert fl.decode_step_bytes(m, {"experts_touched": 0.0}, 0, 0.0) == (
        fl.nonexpert_weight_bytes(m))
    # a token's products: 2 x (53.7 M + 4 x 16 / 128 of an expert) x 9
    per_token = 2 * 9 * (fl.nonexpert_layer_params(m) + 25165824 * 4 / 8)
    assert round(per_token / 9e6, 1) == 132.6  # MFLOP a layer
    assert fl.decode_flops_per_token(m, 8500.0) == pytest.approx(
        per_token + 2 * 16384 * 4096 + 9 * 2 * 8500 * 32 * (2 * 256 + 64))
    # whole rungs: 6.1 / 14.7 / 39.3 TFLOP (attention 8192 n^2 a layer)
    for tokens, tflop in ((4096, 6.1), (8192, 14.7), (16384, 39.4)):
        got = fl.prefill_flops(m, tokens)
        assert got == pytest.approx(
            per_token * tokens + 9 * 8192 * tokens ** 2 + 2 * 16384 * 4096)
        assert round(got / 1e12, 1) == tflop
    fam = importlib.import_module("benchmarks.families." + config["family"])
    assert fam.prefill_flops is fl.prefill_flops
    assert fam.decode_step_bytes is fl.decode_step_bytes


# Two decode steps of a full batch as the engine writes its counts (one step
# late) on zero-length spans, one decode program of 16 ms; three admissions,
# each a DISPATCH of a few milliseconds (the loop runs ahead of the device):
# of 6,000 tokens at the 8,192 rung, whose prefill runs 0.2 s from 20 ms after
# the admission began, of 12,000 at the 16,384 rung (0.6 s, begun after the
# next decode step), and one whose program ran after the trace ended; before
# them a prefill whose admission began before the trace did.
COUNTS = [
    {"occupied": 32, "waiting": 0, "admitted": 0, "retired": 0,
     "host_syncs": 1, "routed_total": 1152, "routed_held": 150,
     "experts_touched": 94},
    {"occupied": 30, "waiting": 0, "admitted": 1, "retired": 1,
     "host_syncs": 2, "routed_total": 1080, "routed_held": 130,
     "experts_touched": 88, "trace_id": "abc"},
]
MS = 1_000_000
HOST = [[["engine.step", 0, 100, {"seq": 0}],
         ["engine.counts", 90, 0, COUNTS[0]],
         ["engine.step", 200, 100, {"seq": 1}],
         ["engine.counts", 290, 0, COUNTS[1]],
         ["engine.admit", 100 * MS, 5 * MS,
          {"prompt_len": 6000, "padded_len": 8192, "slot": 3}],
         ["engine.admit", 330 * MS, 6 * MS,
          {"prompt_len": 12000, "padded_len": 16384, "slot": 4}],
         ["engine.admit", 1030 * MS, 5 * MS,
          {"prompt_len": 9000, "padded_len": 16384, "slot": 5}]]]
DEVICE = {"/device:TPU:0": {
    tr.OPS_LINE: [["fusion.1", 0, 50]],
    tr.MODULES_LINE: [["jit__lambda(1)", 0, 16 * MS],
                      ["jit_prefill_one(0)", 20 * MS, 70 * MS],
                      ["jit_prefill_one(3)", 120 * MS, 200 * MS],
                      ["jit__lambda(2)", 320 * MS, 16 * MS],
                      ["jit_prefill_one(4)", 420 * MS, 600 * MS]]}}


def ctx_of(config, mix, family=None, host=HOST):
    spans = hs.from_planes(host, DEVICE)
    return types.SimpleNamespace(
        host_spans=[spans], trace=tr.Trace.from_planes(DEVICE),
        config=dict(config, family=family or config["family"]), mix=mix,
        stats={"model": config["model"]},
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12})


def read_metric(name, ctx):
    """A metric file's reader on its own arguments, as ``run.py`` calls it."""
    spec = load(HERE, "layer_metrics", name + ".json")
    assert spec["name"] == name and spec["what"]
    reader = importlib.import_module("benchmarks.readers." + spec["reader"])
    return reader.read(ctx, **spec["args"])


def test_prefill_mfu_of_two_prefills_whose_mfu_is_known(config, mix):
    """The two prefills whose admissions are in the trace, each the run
    that began after its admission and before the next: their operations at
    the TRUE lengths over their 0.2 + 0.6 s, against 197 TFLOP/s; the run
    whose admission is not in the trace and the admission whose run is not
    are both left out."""
    m = config["model"]
    got = read_metric("prefill_mfu.serve", ctx_of(config, mix))
    want = (fl.prefill_flops(m, 6000) + fl.prefill_flops(m, 12000)) / 0.8
    assert got == pytest.approx(100 * want / 197e12)
    assert 21 < got < 23  # 34.8 TFLOP in 0.8 s
    # at the rungs' lengths the same runs would read half as much again:
    # padding is not counted as work
    padded = (fl.prefill_flops(m, 8192) + fl.prefill_flops(m, 16384)) / 0.8
    assert 100 * padded / 197e12 > 1.5 * got
    # no admission in the trace, a family without the function (a parent
    # commit's), no trace: nothing to read, and no raise
    assert read_metric("prefill_mfu.serve", ctx_of(
        config, mix, host=[HOST[0][:4]])) is None
    assert read_metric("prefill_mfu.serve",
                       ctx_of(config, mix, family="llama")) is None
    bare = types.SimpleNamespace(**dict(
        vars(ctx_of(config, mix)), trace=None, host_spans=[]))
    assert read_metric("prefill_mfu.serve", bare) is None


def test_the_other_new_metric_files_read_hand_built_spans(config, mix):
    ctx = ctx_of(config, mix)
    assert read_metric("ep8_expert_tokens.serve", ctx) == pytest.approx(
        (150 + 130) / 2 / 144)  # 1.0
    assert read_metric("ep8_experts_touched_pct.serve", ctx) == pytest.approx(
        100 * 91 / 144)  # 63 %
    assert read_metric("prefill_useful_pct.serve_rate", ctx) == pytest.approx(
        100 * (6000 + 12000 + 9000) / (8192 + 16384 + 16384))
    got = read_metric("latent_long_decode_roofline.serve", ctx)
    want = fl.decode_step_bytes(
        config["model"], {"experts_touched": 91.0}, 31.0,
        flops.mean_decode_context(traffic.sizes(mix)))
    assert got == pytest.approx(100 * want / 819e9 / 0.016)
    assert 50 < got < 60  # ~8.9 ms of need over a 16 ms step
    other = ctx_of(config, mix, family="llama")
    for name in ("latent_long_decode_roofline.serve",
                 "ep8_expert_tokens.serve", "ep8_experts_touched_pct.serve"):
        assert read_metric(name, other) is None


def test_the_cell_lists_itself_where_its_metrics_are_true():
    bench = load(ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"], cell["config"]) == (
        1, "longdoc_closed32", "mistral_small4_l9_ep8")
    assert len(cell["why"]) <= 200
    judged = {m["name"] for m in bench["end_to_end"]
              if CELL in m.get("workloads", [])}
    assert judged == {"serve_tokens_per_s"}  # a token gap here is a
    # neighbour's whole prefill: the percentiles stay in the notes
    layer = {m["name"]: m for m in bench["per_layer"]
             if CELL in m.get("workloads", [])}
    assert set(NEW_METRICS) | {"mfu.serve", "decode_step_ms.serve",
                               "cache_read_pct.serve",
                               "replica_ready_s.serve"} <= set(layer)
    assert all(m["moves"] in judged | {"setup_s"} for m in layer.values())
    for name in NEW_METRICS:
        assert layer[name]["workloads"] == [CELL]
        assert layer[name]["moves"] == "serve_tokens_per_s"
        assert os.path.exists(os.path.join(
            HERE, "layer_metrics", name + ".json"))
    # the MiMo cell's list, but for its own three
    mimo = {m["name"] for m in bench["per_layer"]
            if "mimo25_ep16_mixed_closed64" in m.get("workloads", [])}
    assert set(layer) - set(NEW_METRICS) == mimo - {
        "windowed_decode_roofline.serve", "ep16_expert_tokens.serve",
        "ep16_experts_touched_pct.serve"}
    # one four-chip cell, as before
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
