"""``harness/bytes_lm.py``, the one walker of any ``LMConfig``, held to the
eight per-architecture modules it replaced (PR 58). ``data/bytes_pr57.json``
holds what ``harness/bytes_<arch>.py`` of the parent counted, read from the
modules themselves before they were deleted: at each cell's own prefix,
its shortest, middle and longest prompt, its decode steps and sequences,
and a fixed ``experts_read`` a step. Shapes alone: nothing is drawn.

Four agree to the byte. Two differ by small weights the old modules left
out and the step does read (each named below with both numbers). Two were
wrong since PR 51 and the walker is held to the corrected count.

That record cannot be made again (the modules are gone), and ``old_rows``
and ``new_rows`` below restate the walker's own rule. The independent
arithmetic is elsewhere: each configuration's module of ``expanders/`` has a
``check_bytes_..._against_a_hand_count``, a layer's weights, a state and a
row from literal published widths (the hand counts the eight
``test_<arch>_cell_cpu.py`` held their ``bytes_<arch>.py`` to, ported to
the walker), run as cases of ``test_cells_cpu.py``. A ninth configuration
brings its own there and no row here: the eight below are the recorded
ones."""

import json
import os
import types

import pytest

from benchmarks.harness import files
from benchmarks.tests import rehearsal

BENCH = files.Bench(rehearsal.REPO)
WALK = BENCH.load("harness", "bytes_lm")
with open(os.path.join(rehearsal.REPO, "benchmarks", "tests", "data",
                       "bytes_pr57.json")) as fh:
    OLD = json.load(fh)
ARCHES = ["laguna", "qwen3next", "xing4", "lfm2", "mellum2", "ouro",
          "kanana2", "gigachat35"]
CELLS = {"laguna": "sd15_expand_solo",
         "qwen3next": "sd15_qwen3next_expand_solo",
         "xing4": "sd15_xing4_expand_solo", "lfm2": "sd15_lfm2_expand_solo",
         "mellum2": "sd15_mellum2_expand_b4", "ouro": "sd15_ouro_expand_b4",
         "kanana2": "sd15_kanana2_expand_b4",
         "gigachat35": "sd15_gigachat35_expand_b4"}


def lm_config(arch):
    return files.resolve_family(
        BENCH.config(f"sd15_{arch}_expand")).expander


def walked(arch, row):
    old = OLD[arch]
    return WALK.decode_bytes(lm_config(arch), row["first_position"],
                             old["steps"], old["experts_read_per_step"],
                             old["sequences"])


def test_the_recorded_counts_are_of_the_cells_own_traffic():
    assert sorted(OLD) == sorted(ARCHES)
    for arch, old in OLD.items():
        cell = BENCH.cell(CELLS[arch])
        traffic = BENCH.traffic(cell["traffic"])
        args = traffic["payload"]["alwayson_scripts"][
            "prompt expansion"]["args"][0]
        prefix = 1 + len(args["instruction"].split())
        prompts = [len(p.split()) for p in traffic["cycle"]["prompt"]]
        assert [r["first_position"] for r in old["at"]] \
            == [prefix + min(prompts), prefix + 40, prefix + max(prompts)]
        assert old["steps"] == -(-(args["max_new_tokens"] - 1) // 32) * 32
        assert old["sequences"] == traffic["payload"]["batch_size"]


@pytest.mark.parametrize("arch", ["laguna", "qwen3next", "xing4", "lfm2"])
def test_four_configurations_to_the_byte(arch):
    """One sequence a step: a fork of itself attends ``position + 1`` rows,
    and every weight the old module counted the walker counts."""
    cfg, old = lm_config(arch), OLD[arch]
    assert WALK.fixed_bytes(cfg, 1) == old["fixed_bytes"]
    assert WALK.expert_bytes(cfg) == old["expert_bytes"]
    for row in old["at"]:
        assert walked(arch, row) == row["decode_bytes"]


def small_terms(arch):
    """Bytes a step that bytes_kanana2.py and bytes_gigachat35.py left out
    by their own docstrings ("a table row a sequence is left out", "the
    selection bias", "the convolution's taps, A_log and dt_bias") and the
    four older modules counted: the step reads them, so the walker counts
    them in every configuration."""
    cfg, old = lm_config(arch), OLD[arch]
    terms = {"table rows": old["sequences"] * cfg.hidden_size * 2,
             "selection bias": (len(cfg.expert_layers) * cfg.num_experts * 2
                                if cfg.router_bias else 0),
             "taps, A_log, dt_bias": len(cfg.layers_of("linear")) * 2 * (
                 cfg.linear_conv_kernel * cfg.linear_conv_channels
                 + 2 * cfg.linear_num_value_heads)}
    return terms


@pytest.mark.parametrize("arch,want", [
    # 4 x 2 048 x 2 + 7 layers x 128 x 2
    ("kanana2", {"table rows": 16384, "selection bias": 1792,
                 "taps, A_log, dt_bias": 0}),
    # 4 x 7 168 x 2 + 4 layers x 256 x 2 + 4 layers x (4 x 16 384 + 128) x 2
    ("gigachat35", {"table rows": 57344, "selection bias": 2048,
                    "taps, A_log, dt_bias": 525312}),
])
def test_two_configurations_but_for_named_small_weights(arch, want):
    cfg, old = lm_config(arch), OLD[arch]
    terms = small_terms(arch)
    assert terms == want
    more = sum(terms.values())
    # 18 176 B of 2.5 GB a step (7e-6); 584 704 B of 4.4 GB (1.3e-4)
    assert more == {"kanana2": 18176, "gigachat35": 584704}[arch]
    assert WALK.fixed_bytes(cfg, old["sequences"]) \
        == old["fixed_bytes"] + more
    assert WALK.expert_bytes(cfg) == old["expert_bytes"]
    for row in old["at"]:
        got = walked(arch, row)
        assert got == row["decode_bytes"] + old["steps"] * more
        assert got / row["decode_bytes"] - 1 < 1.4e-4


def old_rows(cfg, first, steps, sequences):
    """bytes_ouro.py's and bytes_mellum2.py's count of the rows: every
    sequence ``position + 1`` rows of a full layer and ``min(position + 1,
    window)`` of a window layer, as if a fork had copied the prompt's rows
    once a sequence. It did until PR 51; since then what lies before the
    fork is held once and read once a step (cache/kv.py:fork), so this
    counts the shared range ``sequences`` times where the program can read
    it once: the share read 95 where a count of what the step needs reads
    89, and "under 100 by construction" no longer held."""
    total = 0
    for kind in cfg.layer_types:
        row = WALK.row_bytes(cfg, kind)
        for i in range(steps):
            seen = first + i + 1
            if kind == "sliding":
                seen = min(seen, cfg.sliding_window)
            total += sequences * seen * row
    return total


def new_rows(cfg, first, steps, sequences):
    """The corrected count: the shared range once, ``sequences`` times a
    sequence's own rows; a window layer needs of the shared range only
    what its own rows have not pushed out of the window."""
    total = 0
    for kind in cfg.layer_types:
        row = WALK.row_bytes(cfg, kind)
        for i in range(steps):
            own = i + 1
            shared = first
            if kind == "sliding":
                own = min(own, cfg.sliding_window)
                shared = min(first, cfg.sliding_window - own)
            total += (shared + sequences * own) * row
    return total


@pytest.mark.parametrize("arch", ["ouro", "mellum2"])
def test_two_configurations_to_the_corrected_count(arch):
    cfg, old = lm_config(arch), OLD[arch]
    steps, sequences = old["steps"], old["sequences"]
    table = sequences * cfg.hidden_size * 2   # the old modules left it out
    assert WALK.fixed_bytes(cfg, sequences) == old["fixed_bytes"] + table
    assert WALK.expert_bytes(cfg) == old["expert_bytes"]
    for row in old["at"]:
        first = row["first_position"]
        rest = steps * (old["fixed_bytes"] + old["experts_read_per_step"]
                        * old["expert_bytes"])
        # the old module, term by term, is what was recorded
        assert rest + old_rows(cfg, first, steps, sequences) \
            == row["decode_bytes"]
        # the walker is the same weights and experts, the corrected rows
        assert walked(arch, row) == rest + steps * table \
            + new_rows(cfg, first, steps, sequences)
        assert walked(arch, row) < row["decode_bytes"]
    mid = OLD[arch]["at"][1]
    ratio = walked(arch, mid) / mid["decode_bytes"]
    # what PERF.md predicted of the share before the chip was asked: Ouro
    # 95.0 -> 89.0, Mellum2 91.2 -> 89.6
    assert ratio == pytest.approx({"ouro": 0.9365, "mellum2": 0.9822}[arch],
                                  abs=2e-4)


def test_one_sequence_is_a_fork_of_itself():
    """``bytes_util``'s cells go through ``bytes_util_steps`` now: at one
    sequence the shared range and the own rows add up to ``position + 1``
    in every kind, the window's too."""
    for arch in ARCHES:
        cfg = lm_config(arch)
        for kind in set(cfg.layer_types):
            for first, step in ((0, 0), (100, 0), (528, 383), (2064, 255),
                                (600, 700), (1000, 1100)):
                shared, own = WALK.rows_needed(cfg, kind, first, step)
                seen = first + step + 1
                if kind == "sliding":
                    seen = min(seen, cfg.sliding_window)
                if WALK.row_bytes(cfg, kind):
                    assert shared + own == seen, (arch, kind, first, step)
                assert shared >= 0 and own >= 1


def test_states_are_read_and_written_once_a_sequence():
    cfg = lm_config("gigachat35")
    one = 4 * (64 * 128 * 128 + 3 * 16384)       # 4.39 MB a layer
    assert WALK.state_bytes(cfg, "linear") == one
    step = WALK.step_bytes(cfg, 2064, 0, 0.0, 4)
    assert step["states"] == 2 * 4 * 4 * one
    assert WALK.state_bytes(cfg, "latent") == 0
    lfm = lm_config("lfm2")
    assert WALK.state_bytes(lfm, "conv") == 4 * 2 * 2048
    assert WALK.step_bytes(lfm, 528, 0, 0.0, 1)["states"] \
        == 2 * 8 * 4 * 2 * 2048


def trace_of(launches, seconds, devices=1):
    """What the reader takes of trace_reduce.reduce()'s summary."""
    return {"modules": {"jit_expand_decode_chunk": seconds * devices},
            "module_calls": {"jit_expand_decode_chunk": launches * devices},
            "devices": {i: {} for i in range(devices)}}


def _status(steps, decoded, read):
    return {"serving": {"expander": {
        "decode_steps": steps, "tokens_decoded": decoded,
        "experts_read": read}}}


@pytest.mark.parametrize("arch", ARCHES)
def test_the_one_reader_over_the_one_file(arch):
    spec = BENCH.layer_metric("lm_decode_bytes_util")
    assert spec["reader"] == "bytes_util_steps"
    assert spec["args"] == {"module": "jit_expand_decode_chunk",
                            "needs": "bytes_lm", "steps_per_call": 32}
    entry = next(m for m in BENCH.manifest["per_layer"]
                 if m["name"] == "lm_decode_bytes_util")
    assert CELLS[arch] in entry["workloads"]      # and whoever came later
    reader = BENCH.load("readers", "bytes_util_steps")
    old, cfg = OLD[arch], lm_config(arch)
    traffic = BENCH.traffic(BENCH.cell(CELLS[arch])["traffic"])
    payload = dict(traffic["payload"], prompt=traffic["cycle"]["prompt"][0])
    first = 1 + len(payload["alwayson_scripts"]["prompt expansion"]["args"][
        0]["instruction"].split()) + len(payload["prompt"].split())
    steps, sequences = old["steps"], old["sequences"]
    context = {
        "records": [types.SimpleNamespace(traced=True, payload=payload)],
        "trace": trace_of(steps // 32, 1.5),
        "family": files.resolve_family(BENCH.config(f"sd15_{arch}_expand")),
        "status_before": _status(steps, sequences * steps, 3 * steps),
        "status_after": _status(3 * steps, 3 * sequences * steps,
                                3 * steps + 2 * 8 * steps),
        "chips": 1, "peak": {"hbm_bytes_per_s": 819e9}, "bench": BENCH,
    }
    want = 100 * WALK.decode_bytes(cfg, first, steps, 8.0, sequences) \
        / (1.5 * 819e9)
    assert reader.read(context, **spec["args"]) == pytest.approx(want)
    assert reader.read(dict(context, trace=None), **spec["args"]) is None
    # the trace holds no launch of the executable: nothing to read
    assert reader.read(dict(context, trace=trace_of(0, 1.5)),
                       **spec["args"]) is None
    none = {"serving": {"expander": {"decode_steps": 9,
                                     "tokens_decoded": 9}}}
    assert reader.read(dict(context, status_before=none, status_after=none),
                       **spec["args"]) is None


def reader_context(arch, trace):
    old = OLD[arch]
    traffic = BENCH.traffic(BENCH.cell(CELLS[arch])["traffic"])
    payload = dict(traffic["payload"], prompt=traffic["cycle"]["prompt"][0])
    steps, sequences = old["steps"], old["sequences"]
    return {
        "records": [types.SimpleNamespace(traced=True, payload=payload)],
        "trace": trace,
        "family": files.resolve_family(BENCH.config(f"sd15_{arch}_expand")),
        "status_before": _status(steps, sequences * steps, 3 * steps),
        "status_after": _status(3 * steps, 3 * sequences * steps,
                                3 * steps + 2 * 8 * steps),
        "chips": 1, "peak": {"hbm_bytes_per_s": 819e9}, "bench": BENCH,
    }


@pytest.mark.parametrize("arch", ARCHES)
def test_the_share_is_of_the_launches_the_trace_kept(arch):
    """PR 58's own incident: one traced request of Qwen3-Next, a 178 ms
    host stall, and the profiler's trace came back with eleven of the
    twelve launches of ``jit_expand_decode_chunk`` (602.8 ms for 659.1).
    The request's whole 384 steps over eleven launches' seconds read 97.51
    where every other run read 89.25; two launches lost would have read
    over the 105 % at which the driver refuses a PR. The reader counts the
    bytes of as many launches as the trace holds, the cheapest first."""
    spec = BENCH.layer_metric("lm_decode_bytes_util")
    read = BENCH.load("readers", "bytes_util_steps").read
    launches = OLD[arch]["steps"] // 32
    assert launches in (2, 8, 12)       # Ouro decodes 64 tokens
    a_launch = 0.055                       # seconds; every launch alike
    whole = read(reader_context(arch, trace_of(launches, launches * a_launch)),
                 **spec["args"])
    for kept in sorted({launches - 1, max(1, launches - 2), 1}):
        short = read(reader_context(arch, trace_of(kept, kept * a_launch)),
                     **spec["args"])
        # never over the whole trace's reading, whichever launches went:
        # the kept ones are counted as the cheapest; and under it by less
        # than the rows' part of a step (the weights are most of it)
        assert 0.93 * whole < short <= whole
        # what the reader did before: the whole request over the kept time
        assert whole * launches / kept > 1.08 * short
    # more launches than the request's arguments give (another request's
    # inside the slice): no more bytes, more seconds: low, never high
    more = read(reader_context(arch, trace_of(launches + 3,
                                              (launches + 3) * a_launch)),
                **spec["args"])
    assert more == pytest.approx(whole * launches / (launches + 3))
    # a mesh's devices each launch the program: one launch, not four
    four = read(dict(reader_context(arch, trace_of(launches,
                                                   launches * a_launch, 4)),
                     chips=4), **spec["args"])
    assert four == pytest.approx(whole / 16)    # as ever: seconds summed
    #                                             over devices, times chips


def test_a_layer_kind_the_walker_does_not_know_is_refused():
    """Counted as full attention with no rows and no state it would read a
    plausible wrong share and no test would fail."""
    import dataclasses

    cfg = lm_config("laguna")
    odd = dataclasses.replace(
        cfg, layer_types=("ssm",) + tuple(cfg.layer_types[1:]))
    for call in (lambda: WALK.mixer_bytes(odd, 0),
                 lambda: WALK.row_bytes(odd, "ssm"),
                 lambda: WALK.state_bytes(odd, "ssm"),
                 lambda: WALK.fixed_bytes(odd, 1),
                 lambda: WALK.decode_bytes(odd, 100, 1, 0.0)):
        with pytest.raises(ValueError, match="ssm"):
            call()
    assert WALK.mixer_bytes(odd, 1) == WALK.mixer_bytes(cfg, 1)


def test_every_configurations_layers_are_of_a_kind_the_walker_counts():
    """Every configuration of BENCHMARK.json whose family has an expander,
    the next one's too: nothing here names the eight."""
    seen = 0
    for entry in BENCH.manifest["configs"]:
        model = getattr(files.resolve_family(BENCH.config(entry["name"])),
                        "expander", None)
        if model is None:
            continue
        seen += 1
        assert set(model.layer_types) <= set(WALK.KINDS), entry["name"]
        assert WALK.decode_bytes(model, 64, 2, 1.0, 2) > 0
    assert seen >= 8


def test_the_old_modules_and_the_second_reader_are_gone():
    for arch in ARCHES:
        assert not os.path.exists(BENCH.path("harness", f"bytes_{arch}.py"))
    assert not os.path.exists(BENCH.path("readers", "bytes_util.py"))
