"""What PR 60 added to the yardstick, pinned on the CPU: the Granite-4.0-H
configuration and traffic files, ``lib/flops_granite_h.py``'s arithmetic, and
the three new metric files (on readers that were there) on hand-built spans.
Pure functions and files: no device, no timing.
"""

import importlib
import json
import os
import statistics
import types

import pytest

from benchmarks.lib import flops, flops_granite_h as fl
from benchmarks.lib import host_spans as hs
from benchmarks.lib import trace_reduce as tr
from benchmarks.lib import traffic

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CELL = "granite4h_micro_chat_closed64"
NEW_METRICS = ["ssm_decode_roofline.serve", "prefill_ssm_ms.serve_rate",
               "ssm_chunk_fill_pct.serve"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def load(*path):
    with open(os.path.join(*path)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return load(HERE, "configs", "granite4h_micro.json")


@pytest.fixture(scope="module")
def mix():
    return load(HERE, "traffic", "chat_closed64.json")


def test_the_configuration_is_the_source_with_nothing_cut(config):
    published = config["published"]
    assert config["reduced"] == []
    assert all(config[k] == v for k, v in published.items())
    if os.path.exists(CATALOG):  # the catalog's row, key by key
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "granite-4.0-h-micro")
        assert row["config"] == published
        assert row["source_url"] == config["source"]
    for key in ("assumed", "deployment", "memory", "reduced_why"):
        assert config[key]
    assert "WHOLE" in config["deployment"]
    assert "a deployment's own" in config["deployment"]
    assert "memory_peak_bytes" in config["memory"]
    assert set(config["assumed"]) >= {
        "precision", "mlp_input", "attention_scale", "time_step", "chunk",
        "max_seq", "weights"}
    for n, key in enumerate(("precision", "mlp_input", "attention_scale",
                             "time_step", "chunk", "max_seq", "weights"), 1):
        assert config["assumed"][key].startswith(f"({n})")
    # The program's config IS the published model, size by size.
    m = config["model"]
    same = {"vocab_size": "vocab_size", "d_model": "hidden_size",
            "n_layer": "num_hidden_layers", "n_head": "num_attention_heads",
            "n_kv_head": "num_key_value_heads",
            "d_ff": "shared_intermediate_size",
            "mamba_num_heads": "mamba_n_heads",
            "mamba_head_dim": "mamba_d_head",
            "ssm_state_size": "mamba_d_state", "n_groups": "mamba_n_groups",
            "conv_kernel": "mamba_d_conv", "chunk_size": "mamba_chunk_size",
            "embedding_multiplier": "embedding_multiplier",
            "residual_multiplier": "residual_multiplier",
            "attention_multiplier": "attention_multiplier",
            "logits_scaling": "logits_scaling", "rms_eps": "rms_norm_eps"}
    assert {k: m[k] for k in same} == {k: published[v]
                                       for k, v in same.items()}
    assert m["head_dim"] == published["hidden_size"] // published[
        "num_attention_heads"] == 64
    assert m["mamba_num_heads"] * m["mamba_head_dim"] == (
        published["mamba_expand"] * published["hidden_size"])
    assert (published["num_local_experts"], published["tie_word_embeddings"],
            published["position_embedding_type"], published["mamba_conv_bias"],
            published["mamba_proj_bias"], published["attention_bias"]) == (
        0, True, "nope", True, False, False)
    # all forty layers in the published order
    letters = {"mamba": "M", "attention": "*"}
    assert m["layer_pattern"] == "".join(
        letters[t] for t in published["layer_types"])
    assert m["n_layer"] == len(m["layer_pattern"]) == 40
    assert m["layer_pattern"].count("M") == 36
    assert config["engine"] == {"max_batch_size": 64, "max_seq_len": 2048}
    fam = importlib.import_module("benchmarks.families." + config["family"])
    for name in ("model", "tiny"):
        cfg = fam.config(config[name])
        # the harness's two-layer cut sees two Mamba-2 layers and no
        # attention (PERF.md section 7); both kinds are in the model
        assert cfg.kinds[:2] == "MM" and set(cfg.kinds) == set("M*")
        assert cfg.n_groups == 1
        assert cfg.attention_multiplier != cfg.head_dim ** -0.5
    bench = load(ROOT, "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == config["name"])
    assert entry["reduced"] == [] and entry["source"] == config["source"]
    assert entry["file"] == "benchmarks/configs/granite4h_micro.json"
    assert len(entry["why"]) <= 200
    assert bench["configs"][-1] is entry  # appended, nothing moved


def test_the_traffic_is_short_chat_turns_over_all_four_rungs(config, mix):
    sizes = traffic.sizes(mix)
    eng = config["engine"]
    assert mix["kind"] == "serve_stream" and mix["temperature"] == 0.0
    assert mix["route"] == "/v1/completions"
    assert mix["arrivals"] == {"kind": "closed", "clients": 64}
    assert mix["arrivals"]["clients"] == eng["max_batch_size"]
    assert len(sizes) == mix["population"] == 128
    assert (mix["prompt_tokens"], mix["output_tokens"]) == (
        {"dist": "lognormal", "median": 256, "sigma": 0.9, "min": 32,
         "max": 1536},
        {"dist": "lognormal", "median": 192, "sigma": 0.6, "min": 32,
         "max": 448})
    chat = load(HERE, "traffic", "chat_closed16.json")
    assert mix["prompt_tokens"] == chat["prompt_tokens"]
    assert max(p + o for p, o in sizes) == 1694 < eng["max_seq_len"] - 1
    assert (mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
            == 1984 < eng["max_seq_len"] - 1)
    rungs = [sum(1 for p, _ in sizes if lo < p <= hi) for lo, hi in (
        (0, 256), (256, 512), (512, 1024), (1024, 2048))]
    assert rungs == [63, 39, 19, 7]  # all four are used
    assert sum(o for _, o in sizes) == 27661
    assert 480 < flops.mean_decode_context(sizes) < 490
    tiny = dict(mix, **mix["tiny"])
    assert max(p + o for p, o in traffic.sizes(tiny)) < (
        config["tiny_engine"]["max_seq_len"] - 1)
    a, b = traffic.requests(mix, 6000000019), traffic.requests(mix, 7)
    assert a != b and sorted(r["prompt_tokens"] for r in a) == sorted(
        r["prompt_tokens"] for r in b)


def test_the_population_seed_follows_chat_closed16s_rule(mix):
    """Of seeds 0..399 the draw whose medians and means sit closest to the
    distribution's own (the means after clipping, from a large draw)."""
    import random

    def own_mean(spec):
        rng = random.Random(12345)
        return statistics.fmean(traffic._draw(spec, rng)
                                for _ in range(100000))

    means = [own_mean(mix[k]) for k in ("prompt_tokens", "output_tokens")]
    assert 362 < means[0] < 371 and 215 < means[1] < 220

    def deviation(seed):
        sizes = traffic.sizes(dict(mix, population_seed=seed))
        total = 0.0
        for col, key, mean in zip(zip(*sizes), (
                "prompt_tokens", "output_tokens"), means):
            total += abs(statistics.median(col) - mix[key]["median"]) / mix[
                key]["median"] + abs(statistics.fmean(col) - mean) / mean
        return total

    ranked = sorted(range(400), key=deviation)
    assert ranked[0] == mix["population_seed"] == 383
    assert deviation(383) < 0.045 < 1.0


def test_the_parameter_count_is_the_published_models(config):
    """ISSUE 60's count: a Mamba-2 mixer 25.8 M (in 2048 x 8512, out 4096 x
    2048), an attention 10.49 M, an MLP 50.33 M; 36 x 76.2 + 4 x 60.8 M of
    layers and a table of 205.5 M: 3.19 B parameters, 6.38 GB in bfloat16
    (described as "3B")."""
    m = config["model"]
    assert fl.mamba_params(m) == 2048 * (4096 + 4352 + 64) + 4096 * 2048
    assert round(fl.mamba_params(m) / 1e6, 2) == 25.82
    assert fl.attention_params(m) == 2 * 2048 * 64 * (32 + 8)
    assert round(fl.attention_params(m) / 1e6, 2) == 10.49
    assert fl.mlp_params(m) == 3 * 2048 * 8192
    assert round(fl.mlp_params(m) / 1e6, 2) == 50.33
    assert round(fl.layer_params(m, "M") / 1e6, 2) == 76.15
    assert round(fl.layer_params(m, "*") / 1e6, 2) == 60.82
    assert fl.table_params(m) == 100352 * 2048  # once: the head is tied
    assert round(fl.total_params(m) / 1e9, 2) == 3.19
    assert round(fl.weight_bytes(m) / 1e9, 2) == 6.38
    assert fl.d_inner(m) == 4096 and fl.d_conv(m) == 4352


def test_a_steps_bytes_and_a_prefills_operations_from_the_cells_shapes(
    config, mix
):
    m = config["model"]
    # a slot's state: 36 layers x (64 x 64 x 128 + 3 x 4352) float32
    assert fl.state_bytes_per_slot(m) == 36 * 4 * (524288 + 13056)
    assert round(fl.state_bytes_per_slot(m) / 1e6, 1) == 77.4
    assert round(64 * fl.state_bytes_per_slot(m) / 1e9, 2) == 4.95
    assert fl.kv_bytes_per_token(m) == 4 * 2 * 8 * 64 * 2 == 8192
    assert 64 * 2048 * fl.kv_bytes_per_token(m) == 2 ** 30  # 1.07 GB
    step = fl.decode_step_bytes(m, {}, 62.0, 485.0)
    assert step == pytest.approx(
        fl.weight_bytes(m) + 62 * (2 * fl.state_bytes_per_slot(m)
                                   + 485 * 8192))
    # 6.38 + 9.59 of state + 0.25 of live keys and values = 16.2 GB: 19.8 ms
    assert 19.5 < step / 819e9 * 1e3 < 20.1
    assert fl.decode_step_bytes(m, {}, 0, 0.0) == fl.weight_bytes(m)
    assert fl.decode_flops_per_token(m, 485.0) == pytest.approx(
        2 * fl.total_params(m) + 4 * 4 * 485 * 2048 + 36 * 5 * 4096 * 128)
    # a prompt of 358 tokens: the products are nearly all of its 2.18 TFLOP
    # (the head 0.4 of them), the chunked scan 1.9 %, the triangle less
    got = fl.prefill_flops(m, 358)
    scan = 36 * fl.ssd_chunk_flops(m, 358, m["chunk_size"])
    dense = 36 * fl.layer_params(m, "M") + 4 * fl.layer_params(m, "*")
    assert got == pytest.approx(
        2 * dense * 358 + 4 * 4 * 64 * 32 * 358 * 359 / 2 + scan
        + 2 * 100352 * 2048)
    assert fl.ssd_chunk_flops(m, 1, 256) == (
        256 * 128 + 64 * (256 * 64 + 4 * 64 * 128))
    assert 0.015 < scan / got < 0.02 and 2.1e12 < got < 2.25e12
    fam = importlib.import_module("benchmarks.families." + config["family"])
    assert fam.prefill_flops is fl.prefill_flops
    assert fam.decode_step_bytes is fl.decode_step_bytes
    assert fam.decode_flops_per_token is fl.decode_flops_per_token
    assert not hasattr(fam, "held_expert_slots")  # nothing is routed


# Two decode steps of a full batch as the engine writes its counts (one step
# late) on zero-length spans; the second folded a prefill of 150 tokens at
# the 256 rung; one decode program of 36 ms.
COUNTS = [
    {"occupied": 64, "waiting": 0, "admitted": 0, "retired": 0,
     "host_syncs": 1, "ssm_positions": 64, "ssm_chunk_positions": 64},
    {"occupied": 62, "waiting": 0, "admitted": 1, "retired": 1,
     "host_syncs": 2, "ssm_positions": 62, "ssm_chunk_positions": 64,
     "prefill_ssm_positions": 150, "prefill_ssm_chunk_positions": 256,
     "trace_id": "abc"},
]
MS = 1_000_000
HOST = [[["engine.step", 0, 100, {"seq": 0}],
         ["engine.counts", 90, 0, COUNTS[0]],
         ["engine.step", 200, 100, {"seq": 1}],
         ["engine.counts", 290, 0, COUNTS[1]]]]
DEVICE = {"/device:TPU:0": {
    tr.OPS_LINE: [["fusion.1", 0, 50]],
    tr.MODULES_LINE: [["jit__lambda(1)", 0, 36 * MS],
                      ["jit__lambda(2)", 320 * MS, 36 * MS]]}}


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


def test_the_new_metric_files_read_hand_built_spans(config, mix):
    ctx = ctx_of(config, mix)
    assert read_metric("ssm_chunk_fill_pct.serve", ctx) == pytest.approx(
        100 * 150 / 256)  # the one prefill's; a decode step's are not read
    got = read_metric("ssm_decode_roofline.serve", ctx)
    want = fl.decode_step_bytes(
        config["model"], {}, 63.0,
        flops.mean_decode_context(traffic.sizes(mix)))
    assert got == pytest.approx(100 * want / 819e9 / 0.036)
    assert 50 < got < 60  # ~20 ms of need over a 36 ms step
    mfu = read_metric("mfu.serve", ctx)  # the accepted share of the peak
    assert mfu == pytest.approx(100 * 63 * fl.decode_flops_per_token(
        config["model"], flops.mean_decode_context(traffic.sizes(mix)))
        / 0.036 / 197e12)
    assert 0 < mfu < 105
    # the scope reader's arguments: the Mamba-2 scope of any family, in the
    # prefill program; no table of operations here, so nothing to read
    spec = load(HERE, "layer_metrics", "prefill_ssm_ms.serve_rate.json")
    assert spec["reader"] == "scope_ms_per_run"
    assert spec["args"]["per_module"] == "^jit_prefill_one"
    import re
    scope = re.compile(spec["args"]["scope"])
    assert scope.search("jit(prefill_one)/granite.mamba/while/body/dot")
    assert scope.search("jit(prefill_one)/nemotron.mamba/mul")
    assert not scope.search("jit(prefill_one)/granite.mlp/dot_general")
    assert not scope.search("jit(prefill_one)/granite.mamba_like/dot")
    assert read_metric("prefill_ssm_ms.serve_rate", ctx) is None
    # a family without the functions or the counts (a parent commit's):
    # nothing, no raise
    other = ctx_of(config, mix, family="llama")
    assert read_metric("ssm_decode_roofline.serve", other) is None
    bare = types.SimpleNamespace(**dict(
        vars(ctx), trace=None, host_spans=[]))
    for name in NEW_METRICS:
        assert read_metric(name, bare) is None
    uncounted = types.SimpleNamespace(**dict(vars(ctx), host_spans=[
        hs.from_planes([[["engine.counts", 90, 0, {"occupied": 64}]]],
                       DEVICE)]))
    assert read_metric("ssm_chunk_fill_pct.serve", uncounted) is None


def test_the_cell_lists_itself_where_its_metrics_are_true():
    bench = load(ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert bench["workloads"][-1] is cell  # appended
    assert (cell["chips"], cell["traffic"], cell["config"]) == (
        1, "chat_closed64", "granite4h_micro")
    assert len(cell["why"]) <= 200
    judged = {m["name"] for m in bench["end_to_end"]
              if CELL in m.get("workloads", [])}
    assert judged == {"serve_tokens_per_s"}  # 64 streams share the replica's
    # threads: the percentiles stay in the notes, as in the Nemotron cell
    layer = {m["name"]: m for m in bench["per_layer"]
             if CELL in m.get("workloads", [])}
    assert all(m["moves"] in judged | {"setup_s"} for m in layer.values())
    for name in NEW_METRICS:
        assert layer[name]["workloads"] == [CELL]
        assert layer[name]["moves"] == "serve_tokens_per_s"
        assert layer[name]["layer"] == "model step"
        assert layer[name]["unit"] in ("%", "ms")
        spec = load(HERE, "layer_metrics", name + ".json")
        assert os.path.exists(os.path.join(
            HERE, "readers", spec["reader"] + ".py"))
    # the Olmo-Hybrid cell's list but for what reads a delta rule (by name,
    # so that a metric a later PR gives either cell breaks nothing here)
    assert set(layer) >= {
        "replica_ready_s.serve", "decode_step_ms.serve",
        "device_idle_pct.serve", "decode_step_host_ms.serve",
        "idle_in_sample_pct.serve", "idle_outside_step_pct.serve",
        "queue_wait_ms.serve", "occupied_slots_mean.serve",
        "host_syncs_per_step.serve", "mfu.serve", "chunks_per_write.serve",
        "tokens_per_delta.serve", "replica_spawn_s.serve",
        "engine_build_s.serve", "xla_compile_s.serve",
        "prefill_ms.serve_rate", "cache_read_pct.serve", "prefill_mfu.serve",
        "prefill_useful_pct.serve_rate", "attn_ms.serve", "state_ms.serve",
        "mlp_ms.serve", "head_ms.serve", "unscoped_pct.serve", *NEW_METRICS}
    assert not {"delta_decode_roofline.serve", "experts_ms.serve",
                "prefill_state_ms.serve_rate"} & set(layer)
    # every share of a peak that moves what the cell reports is reported
    assert {"mfu.serve", "prefill_mfu.serve"} <= set(layer)
    # ten cells of 24, one on four chips
    assert len(bench["workloads"]) >= 10
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
