"""The benchmark's join of a device trace with the programs' ``op_name``
tables (``benchmarks/lib/scopes.py``, ``benchmarks/readers/
scope_ms_per_run.py``): pinned numbers on hand-built events in
``benchmarks/lib/trace_sample.json``'s format (``[name, start_ns,
duration_ns]`` a line of a device plane).  CPU only; no timing.
"""

import json
import os
import re
import types

import pytest

from benchmarks.lib import host_spans as hs
from benchmarks.lib import scopes
from benchmarks.lib import trace_reduce as tr
from benchmarks.readers import scope_ms_per_run

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
def part(*names):
    """A metric's ``scope``: ``<family>.<one of names>`` as one component."""
    return r"(?<![\w.])\w+\.(" + "|".join(names) + r")(?![\w.])"


EVERY = part(*scopes.PARTS)


def joined(ops, modules, tables, host=()):
    device = {"/device:TPU:0": {tr.OPS_LINE: ops, tr.MODULES_LINE: modules}}
    return scopes.join([(hs.from_planes(list(host), device), tables)])


# One decode step of 1000 ns, run twice.  ``while.1`` spans two fusions and
# 100 ns of its own (a wrapper: dropped); ``copy.4`` is the compiler's.
STEP = [["while.1", 0, 600], ["fusion.1", 0, 200], ["fusion.2", 250, 300],
        ["fusion.3 tpu_custom_call", 600, 300], ["copy.4", 900, 100]]
STEP_TABLE = {"jit__lambda": [{
    "while.1": "jit(f)/while", "fusion.1": "jit(f)/while/body/fam.attn/dot",
    "fusion.2": "jit(f)/while/body/fam.mlp/jit(ffn)/dot",
    "fusion.3": "jit(f)/fam.head/dot", "copy.4": ""}]}


def two_steps():
    ops = STEP + [[n, s + 2000, d] for n, s, d in STEP]
    return joined(ops, [["jit__lambda(7)", 0, 1000],
                        ["jit__lambda(7)", 2000, 1000]], STEP_TABLE)


def test_self_time_under_a_while_and_shares_that_add_to_100():
    [program] = two_steps().values()
    assert program.name == "jit__lambda" and program.run_ns == [1000, 1000]
    assert program.by_scope() == {"fam.attn": 400, "fam.mlp": 600,
                                  "fam.head": 600, scopes.UNSCOPED: 200}
    assert program.self_ns == 1800 and program.unresolved_ns == 0
    shares = [100.0 * ns / program.self_ns
              for ns in program.by_scope().values()]
    assert sum(shares) == pytest.approx(100.0)


@pytest.mark.parametrize("scope, stat, value", [
    (part("attn", "mla"), "ms", 200 / 1e6),
    (part("mlp", "ffn"), "ms", 300 / 1e6),
    (part("head"), "ms", 300 / 1e6),
    (part("moe", "shared"), "ms", None),  # nothing to read
    (EVERY, "pct_outside", 100.0 * 200 / 1800),
    (part("head"), "pct_outside", 100.0 * 1200 / 1800),
])
def test_the_reader_per_run_of_the_program(scope, stat, value):
    ctx = types.SimpleNamespace(scope_join=two_steps(), trace=object())
    got = scope_ms_per_run.read(ctx, scope, "^jit__lambda", stat)
    assert got == (None if value is None else pytest.approx(value))
    assert scope_ms_per_run.read(ctx, scope, "^jit_step", stat) is None


def test_a_jitted_function_of_a_parts_name_is_not_its_scope():
    assert scopes.scope_of("jit(f)/jit(ffn)/dot") == scopes.UNSCOPED
    assert scopes.scope_of("jit(f)/longcat.ffn/jit(ffn)/dot") == "longcat.ffn"
    assert scopes.scope_of(
        "jit(step)/transpose(jvp(gpt2.attn))/dot_general") == "gpt2.attn"
    assert scopes.scope_of("jit(f)/laguna.attn_full/mul") == "laguna.attn_full"
    assert scopes.scope_of("jit(f)/fam.moe/while/body/fam.shared/x") == \
        "fam.shared"  # the innermost
    assert scopes.scope_of("") == scopes.UNSCOPED
    assert scopes.scope_of(None) == scopes.UNRESOLVED


RUNG = [["fusion.1", 0, 100], ["fusion.2", 100, 300]]


def test_two_rungs_under_one_name_that_agree():
    rows = [{"fusion.1": "jit(p)/fam.moe/dot", "fusion.2": "jit(p)/fam.mla/dot"},
            {"fusion.1": "jit(p)/fam.moe/dot", "fusion.2": "jit(p)/fam.mla/dot",
             "fusion.5": "jit(p)/fam.head/dot"}]
    [program] = joined(RUNG, [["jit_prefill_one(3)", 0, 400]],
                       {"jit_prefill_one": rows}).values()
    assert program.by_scope() == {"fam.moe": 100, "fam.mla": 300}


def test_two_rungs_that_disagree_are_decided_by_coverage():
    small = {"fusion.1": "jit(p)/fam.moe/dot", "fusion.2": "jit(p)/fam.mla/dot"}
    large = {"fusion.1": "jit(p)/fam.mla/dot", "fusion.2": "jit(p)/fam.moe/dot",
             "fusion.9": "jit(p)/fam.head/dot"}
    tables = {"jit_prefill_one": [small, large]}
    # A run with fusion.9 can only be the large rung's.
    [program] = joined(RUNG + [["fusion.9", 400, 50]],
                       [["jit_prefill_one(4)", 0, 450]], tables).values()
    assert program.by_scope() == {"fam.mla": 100, "fam.moe": 300,
                                  "fam.head": 50}
    # A run both cover: no guess.
    programs = joined(RUNG, [["jit_prefill_one(3)", 0, 400]], tables)
    [program] = programs.values()
    assert program.by_scope() == {scopes.UNRESOLVED: 400}
    assert scopes.stat(programs, "^jit_prefill_one", part("moe"), "ms") is None


@pytest.mark.parametrize("stray_ns, readable", [(5, True), (50, False)])
def test_a_name_in_no_table_is_unresolved_and_none_over_one_percent(
        stray_ns, readable):
    ops = STEP[:-1] + [["copy.4", 900, 100 - stray_ns],
                       ["fusion.77", 1000 - stray_ns, stray_ns]]
    programs = joined(ops, [["jit__lambda(7)", 0, 1000]], STEP_TABLE)
    [program] = programs.values()
    assert program.by_scope()[scopes.UNRESOLVED] == stray_ns
    assert program.self_ns == 900
    got = scopes.stat(programs, "^jit__lambda", part("head"), "ms")
    assert got == (pytest.approx(300 / 1e6) if readable else None)
    outside = scopes.stat(programs, "^jit__lambda", EVERY, "pct_outside")
    assert outside == (pytest.approx(100.0 * 100 / 900) if readable else None)


def test_no_table_is_unresolved_whole_and_an_event_in_no_run_is_shown():
    programs = joined(STEP + [["fusion.8", 5000, 10]],
                      [["jit__lambda(7)", 0, 1000]], {})
    assert programs["jit__lambda"].by_scope() == {scopes.UNRESOLVED: 900}
    assert programs["(no run)"].self_ns == 10
    assert scopes.stat(programs, "^jit__lambda", EVERY, "pct_outside") is None
    with pytest.raises(ValueError):
        scopes.stat(programs, "^jit__lambda", EVERY, "median")


def test_tables_are_found_beside_the_trace_and_the_report_adds_up(tmp_path):
    """``stop_profile`` writes ``<path>/programs.jsonl``, the profiler
    ``<path>/plugins/profile/<time>/*.xplane.pb``; a rank's are under its own
    directory."""
    rank = tmp_path / "rank0"
    deep = rank / "plugins" / "profile" / "2026_01_01"
    deep.mkdir(parents=True)
    (rank / scopes.TABLES).write_text(json.dumps(
        {"module": "jit__lambda", "fingerprint": "ab",
         "ops": STEP_TABLE["jit__lambda"][0]}) + "\n")
    found = scopes.tables_beside(str(deep / "vm.xplane.pb"), str(tmp_path))
    assert found == STEP_TABLE
    other = tmp_path / "rank1" / "plugins"
    other.mkdir(parents=True)
    assert scopes.tables_beside(str(other / "vm.xplane.pb"),
                                str(tmp_path)) == {}
    text = "\n".join(scopes.report_program(two_steps()["jit__lambda"]))
    assert "runs=2" in text and "mean=0.0010ms" in text
    rows = {line.split(":")[0].strip(): float(
        re.search(r"([\d.]+)ms a run", line).group(1))
        for line in text.splitlines()
        if line.startswith("  ") and not line.startswith("    ")}
    assert list(rows)[-2:] == [scopes.UNSCOPED, "(sum of the rows)"]
    assert rows.pop("(sum of the rows)") == pytest.approx(sum(rows.values()))
    assert "fusion.3 tpu_custom_call: jit(f)/fam.head/dot" in text


def test_an_idle_gap_is_named_by_the_programs_beside_it_and_the_open_span():
    device = {"/device:TPU:0": {
        tr.OPS_LINE: [["fusion.1", 0, 1_000_000],
                      ["fusion.2", 16_000_000, 1_000_000],
                      ["fusion.3", 17_500_000, 1_000_000]],
        tr.MODULES_LINE: [["jit_sample_logits_greedy(1)", 0, 1_000_000],
                          ["jit__lambda(2)", 16_000_000, 2_500_000]]}}
    host = [[["engine.step", 500_000, 15_000_000, {}],
             ["engine.sample", 600_000, 9_000_000, {}]]]
    f = hs.from_planes(host, device)
    [row] = scopes.report_gaps(f, f.chips[0])  # the 0.5 ms gap is not shown
    assert "idle 15.000ms" in row
    assert "jit_sample_logits_greedy -> jit__lambda" in row
    assert row.endswith("host: engine.step > engine.sample")


def test_the_twelve_metrics_are_declared_with_patterns_that_find_their_parts():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cells = {w["name"]: w for w in bench["workloads"]}
    found = {}
    for metric in bench["per_layer"]:
        with open(os.path.join(REPO, "benchmarks", "layer_metrics",
                               metric["name"] + ".json")) as fh:
            spec = json.load(fh)
        if spec["reader"] == "scope_ms_per_run":
            found[metric["name"]] = (metric, spec["args"])
    # PR 58's twelve, PR 60's prefill_ssm_ms and PR 62's five of the sala
    # scopes
    assert len(found) == 18
    op_names = {
        "attn": "jit(f)/llama.attn/dot", "attn_full": "jit(f)/mimo.attn_full/x",
        "attn_window": "jit(f)/laguna.attn_window/x",
        "mla": "jit(f)/longcat.mla/x", "moe": "jit(f)/mistral4.moe/while/x",
        "shared": "jit(f)/laguna.shared/x", "mlp": "jit(f)/olmo.mlp/x",
        "ffn": "jit(f)/longcat.ffn/jit(ffn)/x",
        "mamba": "jit(f)/nemotron.mamba/x", "delta": "jit(f)/olmo.delta/x",
        "head": "jit(f)/gpt2.head/x", "embed": "jit(f)/gpt2.embed/x",
        "lightning": "jit(f)/sala.lightning/x",
        "sala.attn": "jit(f)/sala.attn/dot",
        "select": "jit(f)/sala.attn/sala.select/top_k",  # INSIDE sala.attn
        "gpt2.attn": "jit(step)/transpose(jvp(gpt2.attn))/dot_general"}
    # the shared patterns know no ``lightning`` (a benchmark PR's to widen):
    # the cell that runs it reads sala_unscoped_pct.serve instead
    shared = set(op_names) - {"lightning"}
    wanted = {"attn_ms.serve": {"attn", "attn_full", "attn_window", "mla",
                                "sala.attn", "select",
                                "gpt2.attn"},  # under jvp(...) too
              "experts_ms.serve": {"moe", "shared"},
              "state_ms.serve": {"mamba", "delta"},
              "mlp_ms.serve": {"mlp", "ffn"}, "head_ms.serve": {"head"},
              "prefill_experts_ms.serve_rate": {"moe", "shared"},
              "prefill_state_ms.serve_rate": {"delta"},
              "prefill_ssm_ms.serve_rate": {"mamba"},
              "attn_ms.train": {"gpt2.attn"}, "mlp_ms.train": set(),
              "head_ms.train": {"head"},
              "lightning_ms.serve": {"lightning"},
              "select_ms.serve": {"select"},
              "prefill_lightning_ms.serve_rate": {"lightning"},
              "prefill_sparse_ms.serve_rate": {"sala.attn", "select"},
              "sala_unscoped_pct.serve": {"lightning", "sala.attn", "select"},
              "unscoped_pct.serve": shared, "unscoped_pct.train": shared}
    for name, (metric, args) in found.items():
        hit = {part for part, op_name in op_names.items()
               if re.search(args["scope"], op_name)}
        assert hit == wanted[name], name
        assert metric["source"] == "device_trace"
        assert metric["layer"] == "model step" and metric["better"] == "lower"
        assert args["stat"] == ("pct_outside" if metric["unit"] == "%" else "ms")
        kind = "train_dp" if name.endswith(".train") else "serve_stream"
        assert metric["moves"] == {"train_dp": "train_tokens_per_s",
                                   "serve_stream": "serve_tokens_per_s"}[kind]
        for cell in metric["workloads"]:
            with open(os.path.join(REPO, "benchmarks", "traffic",
                                   cells[cell]["traffic"] + ".json")) as fh:
                assert json.load(fh)["kind"] == kind
    assert not re.search(found["mlp_ms.train"][1]["scope"], "jit(f)/olmo.mlp/x")
    assert re.search(found["mlp_ms.train"][1]["scope"],
                     "jit(step)/jvp(gpt2.mlp)/dot_general")
