"""What PR 39 added to the yardstick, pinned on the CPU: the Nemotron-H
configuration and traffic files, ``lib/flops_nemotron_h.py``'s arithmetic and
the two readers (``family_decode_roofline``, ``per_held_expert``) on hand-built
spans.  Pure functions and files: no device, no timing.
"""

import importlib
import json
import os
import types

import pytest

from benchmarks.lib import flops, flops_nemotron_h as fl
from benchmarks.lib import host_spans as hs
from benchmarks.lib import trace_reduce as tr
from benchmarks.lib import traffic
from benchmarks.readers import family_decode_roofline, per_held_expert

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CELL = "nemotron3s_ep4_agent_closed64"
# What may differ from the source: the cuts, and nothing that is a width.
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size",
           "num_nextn_predict_layers"]


def load(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return load(HERE, "configs", "nemotron3_super_l11_ep4.json")


@pytest.fixture(scope="module")
def mix():
    return load(HERE, "traffic", "agent_closed64.json")


def test_the_configuration_is_the_source_but_for_its_four_cuts(config):
    published = config["published"]
    assert config["reduced"] == REDUCED
    assert {k for k in published if config[k] != published[k]} == set(REDUCED)
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"], config["num_nextn_predict_layers"]) == (
                11, 128, 32768, 0)
    for key in ("assumed", "deployment", "memory", "reduced_why"):
        assert config[key]
    # The program's config at the published widths, key by key.
    m = config["model"]
    pattern = published["hybrid_override_pattern"]
    assert m["layer_pattern"] == pattern[27:38] == "MEMEMEMEM*E"
    assert m["n_layer"] == len(m["layer_pattern"]) == 11
    # one whole period at the published ratio (40 : 40 : 8 of 88)
    assert [m["layer_pattern"].count(c) * 8 for c in "ME*"] == [
        pattern.count(c) for c in "ME*"]
    same = {"d_model": "hidden_size", "mamba_num_heads": "mamba_num_heads",
            "mamba_head_dim": "mamba_head_dim",
            "ssm_state_size": "ssm_state_size", "n_groups": "n_groups",
            "conv_kernel": "conv_kernel", "chunk_size": "chunk_size",
            "n_head": "num_attention_heads", "head_dim": "head_dim",
            "n_kv_head": "num_key_value_heads",
            "n_routed_experts": "n_routed_experts",
            "top_k": "num_experts_per_tok",
            "moe_latent_size": "moe_latent_size",
            "d_expert": "moe_intermediate_size",
            "d_shared": "moe_shared_expert_intermediate_size",
            "routed_scaling_factor": "routed_scaling_factor",
            "rms_eps": "layer_norm_epsilon"}
    assert {k: m[k] for k in same} == {k: published[v]
                                       for k, v in same.items()}
    assert m["mamba_num_heads"] * m["mamba_head_dim"] == (
        published["expand"] * published["hidden_size"])
    assert (m["experts_held"], m["vocab_size"]) == (128, 32768)
    fam = importlib.import_module("benchmarks.families." + config["family"])
    for name in ("model", "tiny"):
        cfg = fam.config(config[name])
        assert cfg.kinds[:2] == "ME"  # the harness's two-layer check
        assert cfg.experts_held * 2 <= cfg.n_routed_experts
    bench = load(ROOT, "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == config["name"])
    assert entry["reduced"] == REDUCED and entry["source"] == config["source"]


def test_the_traffic_fits_the_cell_and_crosses_the_padded_seam(config, mix):
    sizes = traffic.sizes(mix)
    eng = config["engine"]
    assert len(sizes) == mix["arrivals"]["clients"] == eng["max_batch_size"]
    assert max(p + o for p, o in sizes) < eng["max_seq_len"] - 1
    # every prompt is shorter than its rung: prefill is always padded
    rungs = [sum(1 for p, _ in sizes if lo < p <= hi)
             for lo, hi in ((0, 256), (256, 512))]
    assert rungs == [56, 8] and not any(p in (256, 512) for p, _ in sizes)
    assert 400 < flops.mean_decode_context(sizes) < 410
    tiny = dict(mix, **mix["tiny"])
    assert max(p + o for p, o in traffic.sizes(tiny)) < (
        config["tiny_engine"]["max_seq_len"] - 1)
    a, b = traffic.requests(mix, 3000000019), traffic.requests(mix, 7)
    assert a != b and sorted(r["prompt_tokens"] for r in a) == sorted(
        r["prompt_tokens"] for r in b)


def test_the_parameter_count_is_the_published_models(config):
    """ISSUE 39's count, which agrees with the catalog's "about 78 M a
    layer": Mamba-2 109.6 M, attention 35.7 M, an expert layer outside its
    experts 54.5 M, one expert 5.5 M; (40 x 109.6 + 8 x 35.7 + 40 x 54.5) /
    88 = 77.8 M."""
    pub = dict(config["model"], n_layer=88,
               layer_pattern=config["published"]["hybrid_override_pattern"])
    assert fl.mamba_params(pub) == 4096 * (8192 + 10240 + 128) + 8192 * 4096
    assert round(fl.mamba_params(pub) / 1e6, 1) == 109.6
    assert round(fl.attention_params(pub) / 1e6, 1) == 35.7
    assert round(fl.layer_params(pub, "E") / 1e6, 1) == 54.5
    assert fl.expert_params(pub) == 2 * 1024 * 2688 == 5505024
    mean = sum(fl.layer_params(pub, k) for k in fl.kinds(pub)) / 88
    assert round(mean / 1e6, 1) == 77.8


def test_a_decode_steps_bytes_and_operations_from_the_cells_shapes(config):
    m = config["model"]
    assert fl.kinds(m) == "MEMEMEMEM*E" and fl.held_expert_slots(m) == 640
    # 5 Mamba-2 + 1 attention + 5 x (latent pair + shared) in bf16, five
    # float32 routers, the head: 2.00 GB; the state 21.6 MB a slot
    assert round(fl.nonexpert_weight_bytes(m) / 1e9, 2) == 2.00
    assert fl.state_bytes_per_slot(m) == 4 * 5 * (128 * 64 * 128 + 3 * 10240)
    assert fl.kv_bytes_per_token(m) == 1024
    step = fl.decode_step_bytes(m, {"experts_touched": 600.0}, 64, 405.0)
    assert step == pytest.approx(
        fl.nonexpert_weight_bytes(m) + 600 * 2 * 5505024
        + 64 * (2 * fl.state_bytes_per_slot(m) + 405 * 1024))
    assert 13.0 < step / 819e9 * 1e3 < 14.5  # ms at the v5e's bandwidth
    # idle: no slot, no expert: the weights outside the experts alone
    assert fl.decode_step_bytes(m, {"experts_touched": 0.0}, 0, 0.0) == (
        fl.nonexpert_weight_bytes(m))
    per_token = fl.decode_flops_per_token(m, 405.0)
    dense = sum(fl.layer_params(m, k) for k in fl.kinds(m))
    assert per_token == pytest.approx(
        2 * (dense + 5 * 5505024 * 22 / 4 + 32768 * 4096)
        + 4 * 405 * 32 * 128 + 5 * 5 * 8192 * 128)
    assert 2.2e9 < per_token < 2.4e9


# Two decode steps of a full batch and an idle tail, as the engine writes
# its counts (one step late) on zero-length spans; one decode program of 20 ms.
COUNTS = [
    {"occupied": 64, "waiting": 0, "admitted": 0, "retired": 0,
     "host_syncs": 1, "routed_total": 7040, "routed_held": 1800,
     "experts_touched": 610},
    {"occupied": 62, "waiting": 0, "admitted": 1, "retired": 1,
     "host_syncs": 2, "routed_total": 6820, "routed_held": 1720,
     "experts_touched": 590, "trace_id": "abc"},
]
HOST = [[["engine.step", 0, 100, {"seq": 0}],
         ["engine.counts", 90, 0, COUNTS[0]],
         ["engine.step", 200, 100, {"seq": 1}],
         ["engine.counts", 290, 0, COUNTS[1]]]]
DEVICE = {"/device:TPU:0": {
    tr.OPS_LINE: [["fusion.1", 0, 50]],
    tr.MODULES_LINE: [["jit__lambda(1)", 0, 20_000_000],
                      ["jit__lambda(2)", 30_000_000, 20_000_000],
                      ["jit_prefill_one(3)", 60_000_000, 40_000_000]]}}


def ctx_of(config, mix, host=HOST, family=None):
    spans = hs.from_planes(host, DEVICE)
    return types.SimpleNamespace(
        host_spans=[spans], trace=tr.Trace.from_planes(DEVICE),
        config=dict(config, family=family or config["family"]), mix=mix,
        stats={"model": config["model"]},
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12})


def test_per_held_expert_reads_tokens_and_touched_share(config, mix):
    ctx = ctx_of(config, mix)
    tokens = per_held_expert.read(ctx, "engine.counts", "routed_held")
    assert tokens == pytest.approx((1800 + 1720) / 2 / 640)  # 2.75
    touched = per_held_expert.read(ctx, "engine.counts", "experts_touched",
                                   scale=100.0)
    assert touched == pytest.approx(100 * 600 / 640)
    assert per_held_expert.read(ctx, "engine.counts", "routed_zero") is None
    assert per_held_expert.read(ctx, "engine.nothing", "routed_held") is None
    # a family without the function (a parent commit's): nothing, no raise
    other = ctx_of(config, mix, family="llama")
    assert per_held_expert.read(other, "engine.counts", "routed_held") is None


def test_family_decode_roofline_hands_the_family_the_engines_counts(
        config, mix):
    ctx = ctx_of(config, mix)
    got = family_decode_roofline.read(ctx, "^jit__lambda", "engine.counts")
    want = fl.decode_step_bytes(
        config["model"], {"experts_touched": 600.0}, 63.0,
        flops.mean_decode_context(traffic.sizes(mix)))
    assert got == pytest.approx(100 * want / 819e9 / 0.020)
    assert 60 < got < 75  # ~13.8 ms of need over a 20 ms step
    assert family_decode_roofline.read(
        ctx, "^jit_nothing", "engine.counts") is None
    assert family_decode_roofline.read(
        ctx, "^jit__lambda", "engine.nothing") is None
    other = ctx_of(config, mix, family="llama")
    assert family_decode_roofline.read(
        other, "^jit__lambda", "engine.counts") is None


def test_the_cell_lists_itself_where_its_metrics_are_true():
    bench = load(ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "agent_closed64")
    judged = {m["name"] for m in bench["end_to_end"]
              if CELL in m.get("workloads", [])}
    assert judged == {"serve_tokens_per_s"}
    layer = {m["name"]: m for m in bench["per_layer"]
             if CELL in m.get("workloads", [])}
    assert {"hybrid_decode_roofline.serve", "latent_expert_tokens.serve",
            "experts_touched_pct.serve", "mfu.serve", "decode_step_ms.serve",
            "replica_ready_s.serve"} <= set(layer)
    assert all(m["moves"] in judged | {"setup_s"} for m in layer.values())
    for name in ("hybrid_decode_roofline.serve", "latent_expert_tokens.serve",
                 "experts_touched_pct.serve"):
        assert layer[name]["workloads"] == [CELL]
        spec = load(HERE, "layer_metrics", name + ".json")
        assert hasattr(importlib.import_module(
            "benchmarks.readers." + spec["reader"]), "read")
