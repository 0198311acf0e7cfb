"""`runs/compare_step_programs.py`: two compiled texts are the same program
when they differ only in WHERE the source said each instruction came from."""

import base64
import importlib.util
import io
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def tool():
    spec = importlib.util.spec_from_file_location(
        "compare_step_programs", os.path.join(ROOT, "runs", "compare_step_programs.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _kernel(constant: int, line: int) -> str:
    """A serialized MLIR module (what a Mosaic call's `body` holds)."""
    from jax._src.lib.mlir import ir

    ctx = ir.Context()
    ctx.allow_unregistered_dialects = True
    module = ir.Module.parse(
        f'module {{ "test.op"() {{value = {constant} : i32}} : () -> () loc("learner.py":{line}:3) }}', ctx)
    out = io.BytesIO()
    module.operation.write_bytecode(out)
    return base64.b64encode(out.getvalue()).decode()


def _text(path: str, line: int, shape: str = "f32[8]", constant: int = 7) -> str:
    return "\n".join([
        "HloModule jit_multi, is_scheduled=true",
        "",
        "FileNames",
        f'1 "{path}/learner.py"',
        "",
        "StackFrames",
        f"1 {{file_location_id=1 line={line}}}",
        "",
        "ENTRY %main (p: f32[8]) -> f32[8] {",
        f'  %add.1 = {shape} add(%p, %p), metadata={{op_name="jit(multi)/add" stack_frame_id={line}}}',
        '  ROOT %_lstm_fwd_call.1 = f32[8] custom-call(%add.1), custom_call_target="tpu_custom_call", '
        f'backend_config={{"custom_call_config":{{"body":"{_kernel(constant, line)}","needs_hlo_passes":false}}}}, '
        f'metadata={{op_name="jit(multi)/kernel" stack_frame_id={line + 1}}}',
        "}",
    ])


@pytest.mark.parametrize("other, equal", [
    (dict(path="/elsewhere", line=99), True),                # source positions only
    (dict(path="/a", line=12, shape="f32[16]"), False),      # an instruction
    (dict(path="/a", line=12, constant=8), False),           # inside a kernel
], ids=["positions-only", "instruction", "kernel-op"])
def test_source_positions_are_all_that_is_dropped(tool, other, equal):
    a, b = _text("/a", 12), _text(**other)
    assert a != b
    assert (tool.without_source_positions(a) == tool.without_source_positions(b)) == equal
    kept = tool.without_source_positions(a)
    assert "metadata" not in kept and "learner.py" not in kept and "%add.1 = f32[8] add" in kept
