"""What PR 67 added to the yardstick, pinned on the CPU: the Kimi-Linear
configuration, the two traffic files (``rollout_closed64`` and the decode-only
``decode_closed16`` of the Mistral configuration), ``lib/flops_kimi_linear.py``'s
arithmetic, and the two new metric files (one on a reader that was there,
one on ``readers/kernel_bytes_roofline.py``) on hand-built spans.  Pure
functions and files: no device, no timing.
"""

import importlib
import json
import os
import random
import statistics
import types

import pytest

from benchmarks.lib import flops, flops_kimi_linear as fl
from benchmarks.lib import host_spans as hs
from benchmarks.lib import trace_reduce as tr
from benchmarks.lib import traffic

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CELL, DECODE = "kimilinear_ep16_rollout_closed64", "mistral16_decode_closed16"
NEW_METRICS = ["kimi_decode_roofline.serve", "kda_update_roofline.serve"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def load(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return load(HERE, "configs", "kimi_linear_l21_ep16.json")


@pytest.fixture(scope="module")
def mix():
    return load(HERE, "traffic", "rollout_closed64.json")


@pytest.fixture(scope="module")
def decode_mix():
    return load(HERE, "traffic", "decode_closed16.json")


def test_the_configuration_is_the_source_cut_in_depth_experts_and_rows(config):
    published = config["published"]
    cut = ["num_hidden_layers", "num_experts", "vocab_size"]
    assert config["reduced"] == cut
    assert {k for k, v in published.items() if config[k] != v} == set(cut)
    assert [(published[k], config[k]) for k in cut] == [
        (27, 21), (256, 16), (163840, 20480)]
    if os.path.exists(CATALOG):  # the catalog's row, key by key
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
        assert row["config"] == published
        assert row["source_url"] == config["source"]
    m, linear = config["model"], published["linear_attn_config"]
    # the published order, letter for letter, and the rotation: layer 1
    # and layers 4-23
    kinds = "".join("M" if i + 1 in linear["full_attn_layers"] else "K"
                    for i in range(published["num_hidden_layers"]))
    assert sorted(linear["full_attn_layers"] + linear["kda_layers"]) == list(
        range(1, 28))
    from ray_tpu.models.kimi_linear import PUBLISHED_PATTERN
    assert kinds == PUBLISHED_PATTERN
    assert m["layer_pattern"] == kinds[0] + kinds[3:23] == "K" + "MKKK" * 5
    assert m["n_layer"] == 21 == len(m["layer_pattern"])
    assert m["first_k_dense"] == published["first_k_dense_replace"] == 1
    assert fl.kinds(m)[:2] == "kM"  # what the harness's two-layer cut sees
    # every width as published
    assert (m["d_model"], m["d_ff"], m["d_expert"]) == (
        published["hidden_size"], published["intermediate_size"],
        published["moe_intermediate_size"])
    assert (m["n_head"], m["kv_lora_rank"], m["qk_nope_head_dim"],
            m["qk_rope_head_dim"], m["v_head_dim"]) == (
        published["num_attention_heads"], published["kv_lora_rank"],
        published["qk_nope_head_dim"], published["qk_rope_head_dim"],
        published["v_head_dim"])
    assert published["q_lora_rank"] is None and published["mla_use_nope"]
    assert (m["linear_num_heads"], m["linear_head_dim"], m["conv_kernel"]) == (
        linear["num_heads"], linear["head_dim"],
        linear["short_conv_kernel_size"])
    assert m["gate_rank"] == linear["head_dim"]
    assert (m["n_routed_experts"], m["top_k"], m["routed_scaling_factor"],
            m["rms_eps"]) == (
        published["num_experts"], published["num_experts_per_token"],
        published["routed_scaling_factor"], published["rms_norm_eps"])
    assert published["num_shared_experts"] == 1
    assert published["num_expert_group"] == published["topk_group"] == 1
    assert published["moe_renormalize"] is True
    assert published["moe_router_activation_func"] == "sigmoid"
    # the share: 16 of 256 from 112, an eighth of the rows
    assert (m["experts_held"], m["expert_offset"]) == (16, 112)
    assert m["vocab_size"] * 8 == published["vocab_size"]
    for key in ("assumed", "deployment", "memory", "reduced_why"):
        assert config[key]
    assert "16 chips" in config["deployment"]
    assert "layers 2-3 and 24-27 are left out" in config["reduced_why"]
    assert set(config["assumed"]) >= {
        "kda_convolution", "kda_decay", "kda_output_gate", "kda_beta",
        "kda_l2", "mla", "router", "chunk", "precision", "max_seq",
        "weights"}
    assert config["engine"] == {"max_batch_size": 64, "max_seq_len": 4096}
    tiny = config["tiny"]
    assert fl.kinds(tiny) == "kMKKKM" and tiny["experts_held"] == 8
    bench = load(ROOT, "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == config["name"])
    assert entry["reduced"] == cut and entry["source"] == config["source"]
    assert entry["file"] == "benchmarks/configs/kimi_linear_l21_ep16.json"
    assert len(entry["why"]) <= 200
    names = [c["name"] for c in bench["configs"]]
    assert names.index(config["name"]) == names.index("minicpm_sala_l12") + 1
    # the program's config takes the file's model as it stands
    fam = importlib.import_module("benchmarks.families." + config["family"])
    assert fam.config(m).kinds == fl.kinds(m)
    assert fam.config(tiny).kinds == fl.kinds(tiny)


def test_the_rollout_traffic_is_a_task_in_and_a_worked_answer_out(config, mix):
    sizes = traffic.sizes(mix)
    eng = config["engine"]
    assert mix["kind"] == "serve_stream" and mix["temperature"] == 0.0
    assert mix["route"] == "/v1/completions"
    assert mix["arrivals"] == {"kind": "closed", "clients": 64}
    assert mix["arrivals"]["clients"] == eng["max_batch_size"]
    assert len(sizes) == mix["population"] == 64
    assert (mix["prompt_tokens"], mix["output_tokens"]) == (
        {"dist": "lognormal", "median": 512, "sigma": 0.6, "min": 128,
         "max": 1536},
        {"dist": "lognormal", "median": 1024, "sigma": 0.5, "min": 384,
         "max": 2048})  # as ISSUE 67 issued it: no fallback was taken
    rungs = [next(r for r in (256, 512, 1024, 2048, 4096) if r >= p)
             for p, _ in sizes]
    assert {r: rungs.count(r) for r in set(rungs)} == {
        256: 12, 512: 20, 1024: 24, 2048: 8}
    assert max(p + o for p, o in sizes) == 2868 < eng["max_seq_len"] - 1
    assert (mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
            == 3584 < eng["max_seq_len"] - 1)
    assert sum(o for _, o in sizes) == 71893
    assert 1225 < flops.mean_decode_context(sizes) < 1232
    tiny_sizes = traffic.sizes(dict(mix, **mix["tiny"]))
    assert max(p + o for p, o in tiny_sizes) < (
        config["tiny_engine"]["max_seq_len"] - 1)
    a, b = traffic.requests(mix, 6700000019), traffic.requests(mix, 7)
    assert a != b and sorted(r["prompt_tokens"] for r in a) == sorted(
        r["prompt_tokens"] for r in b)


def test_the_decode_traffic_is_short_instructions_and_long_generations(
        decode_mix):
    mistral = load(HERE, "configs", "mistral7b_l16.json")
    sizes = traffic.sizes(decode_mix)
    assert decode_mix["kind"] == "serve_stream"
    assert decode_mix["arrivals"] == {"kind": "closed", "clients": 16}
    assert mistral["engine"]["max_batch_size"] == 16
    assert len(sizes) == decode_mix["population"] == 16
    assert (decode_mix["prompt_tokens"], decode_mix["output_tokens"]) == (
        {"dist": "lognormal", "median": 48, "sigma": 0.2, "min": 32,
         "max": 64},
        {"dist": "lognormal", "median": 768, "sigma": 0.25, "min": 512,
         "max": 1024})
    assert all(p <= 256 for p, _ in sizes)  # every prompt the lowest rung
    assert max(p + o for p, o in sizes) == 1088 < (
        mistral["engine"]["max_seq_len"] - 1)
    assert sum(o for _, o in sizes) == 12435
    tiny_sizes = traffic.sizes(dict(decode_mix, **decode_mix["tiny"]))
    assert max(p + o for p, o in tiny_sizes) < (
        mistral["tiny_engine"]["max_seq_len"] - 1)


@pytest.mark.parametrize("name,seed,runner_up", [
    ("rollout_closed64", 172, 0.035), ("decode_closed16", 307, 0.022)])
def test_the_population_seeds_follow_chat_closed16s_rule(name, seed, runner_up):
    """Of seeds 0..399 the draw whose medians and means sit closest to the
    distribution's own (the means after clipping, from a large draw)."""
    mix = load(HERE, "traffic", name + ".json")

    def own_mean(spec):
        rng = random.Random(12345)
        return statistics.fmean(traffic._draw(spec, rng)
                                for _ in range(100000))

    means = [own_mean(mix[k]) for k in ("prompt_tokens", "output_tokens")]

    def deviation(seed):
        sizes = traffic.sizes(dict(mix, population_seed=seed))
        return sum(
            abs(statistics.median(col) - mix[key]["median"])
            / mix[key]["median"] + abs(statistics.fmean(col) - mean) / mean
            for col, key, mean in zip(zip(*sizes), (
                "prompt_tokens", "output_tokens"), means))

    order = sorted(range(400), key=deviation)
    assert order[0] == mix["population_seed"] == seed
    assert deviation(seed) < 0.02 < runner_up < deviation(order[1])


def test_the_parameter_count_is_the_published_models(config):
    """ISSUE 67's count: a KDA mixer 39.5 M, a latent-attention mixer
    29.1 M, an expert 7.08 M, layer 1's MLP 63.7 M; the published 27 layers,
    256 experts and two tables 49 B with 3.1 B active a token; the cut 6.54
    GB of layers and 0.19 GB of vocabulary in bfloat16."""
    m = config["model"]
    assert fl.kda_params(m) == (2304 * 3 * 4096 + 2 * (2304 * 128 + 128 * 4096)
                                + 2304 * 32 + 4096 * 2304)
    assert round(fl.kda_params(m) / 1e6, 1) == 39.5
    assert fl.mla_params(m) == (2304 * 32 * 192 + 2304 * 576
                                + 512 * 32 * 256 + 4096 * 2304)
    assert round(fl.mla_params(m) / 1e6, 1) == 29.1
    assert fl.expert_params(m) == 3 * 2304 * 1024
    assert fl.dense_params(m) == 3 * 2304 * 9216
    assert {s: fl.count(m, s) for s in ("kda", "mla", "dense", "moe")} == {
        "kda": 16, "mla": 5, "dense": 1, "moe": 20}
    assert fl.held_expert_slots(m) == 16 * 20
    whole = dict(m, layer_pattern="KKKM" * 6 + "KKM", n_layer=27,
                 experts_held=256, expert_offset=0, vocab_size=163840)
    total = (fl.nonexpert_params(whole)
             + fl.held_expert_slots(whole) * fl.expert_params(whole)
             + fl.count(whole, "moe") * fl.router_params(whole)
             + 2 * whole["vocab_size"] * whole["d_model"])
    assert round(total / 1e9, 1) == 49.1
    active = (fl.nonexpert_params(whole)
              + fl.count(whole, "moe") * 8 * fl.expert_params(whole)
              + whole["vocab_size"] * whole["d_model"])
    assert round(active / 1e9, 1) == 3.1
    cut = 2 * (fl.nonexpert_params(m)
               + fl.held_expert_slots(m) * fl.expert_params(m)) + 4 * (
        fl.count(m, "moe") * fl.router_params(m))
    assert round(cut / 1e9, 2) == 6.54
    assert round(2 * 2 * m["vocab_size"] * m["d_model"] / 1e9, 2) == 0.19


def test_a_steps_bytes_and_a_prefills_operations_from_the_cells_shapes(
        config, mix):
    m = config["model"]
    # a slot's state: 16 layers x (32 heads x 128 x 128 + 3 x 12288) float32
    assert fl.kda_state_bytes(m) == 4 * 32 * 128 * 128
    assert fl.state_bytes_per_slot(m) == 16 * 4 * (32 * 128 * 128 + 3 * 12288)
    assert round(64 * fl.state_bytes_per_slot(m) / 1e9, 2) == 2.30
    assert fl.latent_bytes_per_position(m) == 5 * 576 * 2
    assert round(64 * 4096 * fl.latent_bytes_per_position(m) / 1e9, 2) == 1.51
    context = flops.mean_decode_context(traffic.sizes(mix))
    step = fl.decode_step_bytes(m, {"experts_touched": 20 * 13.8}, 64.0,
                                context)
    assert step == pytest.approx(
        fl.nonexpert_weight_bytes(m) + 2 * 276 * fl.expert_params(m) + 64 * (
            2 * fl.state_bytes_per_slot(m) + context * 5760))
    assert 13.4 < step / 819e9 * 1e3 < 13.6  # ISSUE 67's 13.5 ms
    # the state, read and written, is the largest share of it
    assert 0.40 < 64 * 2 * fl.state_bytes_per_slot(m) / step < 0.43
    assert fl.decode_step_bytes(m, {"experts_touched": 0}, 0, 0.0) == (
        fl.nonexpert_weight_bytes(m))
    assert fl.kda_update_bytes(m, 64.0) == 64 * 16 * 2 * 4 * 32 * 128 * 128
    assert round(fl.kda_update_bytes(m, 64.0) / 819e9 * 1e3, 2) == 5.24
    routed = 20 * fl.expert_params(m) * 8 * 16 / 256
    assert fl.decode_flops_per_token(m, context) == pytest.approx(
        2 * (fl.nonexpert_params(m) + 20 * fl.router_params(m) + routed
             + 20480 * 2304)
        + 5 * 2 * context * 32 * (2 * 512 + 64) + 16 * 7 * 4096 * 128)
    got = fl.prefill_flops(m, 600)
    rule = 16 * fl.kda_chunk_flops(m, 600, 32)
    assert got == pytest.approx(
        2 * (fl.nonexpert_params(m) + 20 * fl.router_params(m) + routed) * 600
        + 5 * 2 * (600 * 600 / 2) * 32 * (128 + 64 + 128) + rule
        + 2 * 20480 * 2304)
    assert 1.3e12 < got < 1.4e12 and 0.02 < rule / got < 0.04
    fam = importlib.import_module("benchmarks.families." + config["family"])
    assert fam.prefill_flops is fl.prefill_flops
    assert fam.decode_step_bytes is fl.decode_step_bytes
    assert fam.decode_flops_per_token is fl.decode_flops_per_token
    assert fam.held_expert_slots is fl.held_expert_slots
    assert fam.kda_update_bytes is fl.kda_update_bytes


# Two decode steps of a full batch as the engine writes its counts (one step
# late) on zero-length spans; the second folded a prefill of 600 tokens at
# the 1024 rung; one decode program of 16 ms, of which sixteen kernels of
# 0.4 ms.
COUNTS = [
    {"occupied": 64, "waiting": 0, "admitted": 0, "retired": 0,
     "host_syncs": 1, "delta_positions": 64, "delta_chunk_positions": 64,
     "routed_total": 64 * 8 * 20, "routed_held": 640,
     "experts_touched": 280, "held_chunks": 0, "held_chunk_rows": 0},
    {"occupied": 63, "waiting": 0, "admitted": 1, "retired": 1,
     "host_syncs": 2, "delta_positions": 63, "delta_chunk_positions": 64,
     "routed_total": 63 * 8 * 20, "routed_held": 620,
     "experts_touched": 272, "held_chunks": 0, "held_chunk_rows": 0,
     "prefill_delta_positions": 600, "prefill_delta_chunk_positions": 1024,
     "prefill_routed_total": 600 * 8 * 20, "prefill_routed_held": 6000,
     "prefill_experts_touched": 320, "prefill_held_chunks": 330,
     "prefill_held_chunk_rows": 330 * 128, "trace_id": "abc"},
]
MS = 1_000_000
HOST = [[["engine.step", 0, 100, {"seq": 0}],
         ["engine.counts", 90, 0, COUNTS[0]],
         ["engine.step", 200, 100, {"seq": 1}],
         ["engine.counts", 290, 0, COUNTS[1]]]]
KERNEL = ('%fusion.9 = (f32[16,64,32,128,128]) custom-call(...), '
          'custom_call_target="tpu_custom_call"')
DEVICE = {"/device:TPU:0": {
    tr.OPS_LINE: [["fusion.1", 0, 50]] + [
        [KERNEL, run * 320 * MS + 1000 + i * 500_000, 400_000]
        for run in range(2) for i in range(16)],
    tr.MODULES_LINE: [["jit__lambda(1)", 0, 16 * MS],
                      ["jit__lambda(2)", 320 * MS, 16 * MS]]}}


def ctx_of(config, mix, family=None):
    return types.SimpleNamespace(
        host_spans=[hs.from_planes(HOST, DEVICE)],
        trace=tr.Trace.from_planes(DEVICE),
        config=dict(config, family=family or config["family"]), mix=mix,
        stats={"model": config["model"]},
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12})


def read_metric(name, ctx):
    """A metric file's reader on its own arguments, as ``run.py`` calls it."""
    spec = load(HERE, "layer_metrics", name + ".json")
    assert spec["name"] == name and spec["what"]
    reader = importlib.import_module("benchmarks.readers." + spec["reader"])
    return reader.read(ctx, **spec["args"])


def test_the_metric_files_read_hand_built_spans(config, mix):
    ctx = ctx_of(config, mix)
    context = flops.mean_decode_context(traffic.sizes(mix))
    got = read_metric("kimi_decode_roofline.serve", ctx)
    want = fl.decode_step_bytes(
        config["model"], {"experts_touched": 276.0}, 63.5, context)
    assert got == pytest.approx(100 * want / 819e9 / 0.016)
    assert 80 < got < 90  # ~13.5 ms of need over a 16 ms step
    kernel = read_metric("kda_update_roofline.serve", ctx)
    assert kernel == pytest.approx(
        100 * fl.kda_update_bytes(config["model"], 63.5) / 819e9 / 0.0064)
    assert 80 < kernel < 85  # 5.2 ms of need over sixteen kernels of 0.4
    mfu = read_metric("mfu.serve", ctx)  # the accepted share of the peak
    assert mfu == pytest.approx(100 * 63.5 * fl.decode_flops_per_token(
        config["model"], context) / 0.016 / 197e12)
    assert 0 < mfu < 105
    # the accepted counters the cell joins, on the same spans
    assert read_metric("ep16_expert_tokens.serve", ctx) == pytest.approx(
        630 / 320)  # 64 x 8 / 256 = 2.0 expected
    assert read_metric("ep16_experts_touched_pct.serve", ctx) == (
        pytest.approx(100 * 276 / 320))
    assert read_metric("delta_chunk_fill_pct.serve", ctx) == pytest.approx(
        100 * 600 / 1024)
    assert read_metric("held_loop_turns.serve", ctx) == 0.0
    # the scope readers the cell joins find this family's scopes
    import re
    for name, part in (("state_ms.serve", "delta"), ("attn_ms.serve", "mla"),
                       ("experts_ms.serve", "moe"),
                       ("experts_ms.serve", "shared"),
                       ("mlp_ms.serve", "mlp"), ("head_ms.serve", "head"),
                       ("prefill_state_ms.serve_rate", "delta"),
                       ("prefill_experts_ms.serve_rate", "moe"),
                       ("unscoped_pct.serve", "embed")):
        scope = load(HERE, "layer_metrics", name + ".json")["args"]["scope"]
        assert re.search(scope, f"jit(f)/kimi.{part}/while/body/dot"), name
    # a family without the functions (a parent commit's): nothing, no raise
    other = ctx_of(config, mix, family="llama")
    for name in NEW_METRICS:
        assert read_metric(name, other) is None
    bare = types.SimpleNamespace(**dict(vars(ctx), trace=None, host_spans=[]))
    for name in NEW_METRICS:
        assert read_metric(name, bare) is None
    no_kernel = dict(DEVICE["/device:TPU:0"], **{
        tr.OPS_LINE: [["fusion.1", 0, 50]]})
    quiet = types.SimpleNamespace(**dict(vars(ctx), trace=tr.Trace.from_planes(
        {"/device:TPU:0": no_kernel})))
    assert read_metric("kda_update_roofline.serve", quiet) is None


def test_both_cells_list_themselves_where_their_metrics_are_true():
    bench = load(ROOT, "BENCHMARK.json")
    names = [w["name"] for w in bench["workloads"]]
    assert names.index(CELL) == names.index(
        "mistral16_longprompt_closed16") + 1 == names.index(DECODE) - 1
    cells = {w["name"]: w for w in bench["workloads"]}
    assert (cells[CELL]["chips"], cells[CELL]["traffic"],
            cells[CELL]["config"]) == (1, "rollout_closed64",
                                       "kimi_linear_l21_ep16")
    assert (cells[DECODE]["chips"], cells[DECODE]["traffic"],
            cells[DECODE]["config"]) == (1, "decode_closed16",
                                         "mistral7b_l16")
    assert all(len(cells[c]["why"]) <= 200 for c in (CELL, DECODE))
    for cell in (CELL, DECODE):
        judged = {m["name"] for m in bench["end_to_end"]
                  if cell in m.get("workloads", [])}
        assert judged == {"serve_tokens_per_s"}
    layer = {m["name"]: m for m in bench["per_layer"]
             if CELL in m.get("workloads", [])}
    assert all(m["moves"] in ("serve_tokens_per_s", "setup_s")
               for m in layer.values())
    for name in NEW_METRICS:
        assert layer[name]["workloads"] == [CELL]
        assert layer[name]["moves"] == "serve_tokens_per_s"
        assert layer[name]["unit"] == "%"
        spec = load(HERE, "layer_metrics", name + ".json")
        assert os.path.exists(os.path.join(
            HERE, "readers", spec["reader"] + ".py"))
    assert set(layer) == {
        "replica_ready_s.serve", "decode_step_ms.serve",
        "device_idle_pct.serve", "decode_step_host_ms.serve",
        "idle_in_sample_pct.serve", "idle_outside_step_pct.serve",
        "queue_wait_ms.serve", "occupied_slots_mean.serve",
        "host_syncs_per_step.serve", "mfu.serve", "chunks_per_write.serve",
        "tokens_per_delta.serve", "replica_spawn_s.serve",
        "engine_build_s.serve", "xla_compile_s.serve",
        "prefill_ms.serve_rate", "prefill_mfu.serve",
        "prefill_useful_pct.serve_rate", "cache_read_pct.serve",
        "attn_ms.serve", "state_ms.serve", "experts_ms.serve",
        "mlp_ms.serve", "head_ms.serve", "unscoped_pct.serve",
        "prefill_state_ms.serve_rate", "prefill_experts_ms.serve_rate",
        "delta_chunk_fill_pct.serve", "held_loop_turns.serve",
        "ep16_expert_tokens.serve", "ep16_experts_touched_pct.serve",
        *NEW_METRICS}
    # every share of a peak that moves what the cell reports is reported
    assert {"mfu.serve", "prefill_mfu.serve"} <= set(layer)
    # the decode-only cell: the long-prompt cell's lists less the two that
    # read a prefill in every traced window
    longprompt = {m["name"] for m in bench["per_layer"]
                  if "mistral16_longprompt_closed16" in m.get("workloads", [])}
    decode = {m["name"] for m in bench["per_layer"]
              if DECODE in m.get("workloads", [])}
    assert len(longprompt) == 23
    assert longprompt - decode == {"prefill_ms.serve_rate",
                                   "prefill_useful_pct.serve_rate"}
    assert decode < longprompt and "mfu.serve" in decode
    # fourteen cells of 24, one on four chips
    assert len(bench["workloads"]) >= 14
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
