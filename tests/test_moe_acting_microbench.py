"""`runs/moe_acting_microbench.py --allow-cpu`: the step-0 microbenchmark of PR
59 runs end to end at tiny widths on the CPU (a smoke test of its paths, not a
reading: its numbers mean something on the chip only), and every form of the
held experts' part that it times gives the queue's output for 16 tokens."""

import importlib.util
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _microbench():
    spec = importlib.util.spec_from_file_location(
        "moe_acting_microbench", os.path.join(ROOT, "runs", "moe_acting_microbench.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("cell", ["nemotron", "qwen3-next"])
def test_every_form_gives_the_queues_output_for_an_acting_steps_tokens(cell, capsys):
    bench = _microbench()
    rc = bench.main(["--allow-cpu", "--steps", "3", "--cells", cell])
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    assert rc == 0
    floor, readings = lines[0], lines[1:]
    assert (floor["case"], floor["N"], floor["capacity"], floor["held"]) == ("floor", 16, 128, 4)
    assert [l["form"] for l in readings] == [*bench.FORMS, "committed"] and list(bench.FORMS)[0] == "queue"
    for l in readings:
        assert l["us_a_layer_step"] > 0
        # the same products, summed over at most `held` experts in another order
        assert l["max_abs_diff_from_first_form"] <= 2e-5 * l["max_abs"]


def test_the_floor_is_the_issues_bytes_at_published_widths():
    """ISSUE 59's table: 159.6 + 39.9 + 1.4 MB a layer-step (nemotron), 100.7 + 6.3 + 4.2 (qwen3-next)."""
    from benchmark import harness
    from r2d2_tpu.models import hybrid_stack as hs

    bench = _microbench()
    got = {}
    for cell, config in bench.CELLS.items():
        cfg = harness.build_config(harness.load_json(os.path.join(ROOT, "benchmark", "configs", config + ".json")), 0)
        got[cell] = bench.floor_us(hs.spec_of(cfg).sizes("E"))
    mb = lambda cell: [round(got[cell]["weights_mb"][k], 1) for k in ("held", "shared", "router")]
    assert mb("nemotron") == [159.6, 39.9, 1.4] and mb("qwen3-next") == [100.7, 6.3, 4.2]
    assert round(got["nemotron"]["floor_us"]) == 245 and round(got["qwen3-next"]["floor_us"]) == 136


def test_it_reads_nothing_without_a_chip(capsys):
    assert _microbench().main(["--cells", "nemotron"]) == 3
    assert "no TPU" in capsys.readouterr().err
