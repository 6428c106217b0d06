"""Dry-run machinery smoke test: lower + compile one cell per step-kind on
a small fake-device mesh in a subprocess (XLA device count must be set
before jax initialises, hence the isolation)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import json
    import jax
    from jax.sharding import AxisType
    from repro.launch.cells import build_cell
    from repro.launch.hlo_analysis import analyze

    out = {}
    mesh = jax.make_mesh((4, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    for arch, shape in [("gemma3-1b", "train_4k"),
                        ("granite-moe-1b-a400m", "decode_32k"),
                        ("whisper-tiny", "prefill_32k")]:
        cell = build_cell(arch, shape, mesh)
        with mesh:
            comp = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                           out_shardings=cell.out_shardings
                           ).lower(*cell.args).compile()
        a = analyze(comp.as_text())
        out[f"{arch}|{shape}"] = {"flops": a["flops"],
                                  "coll": a["collective_wire_total"]}
    print("RESULT:" + json.dumps(out))
""")


@pytest.mark.slow
def test_dryrun_cells_compile_on_mini_mesh():
    proc = subprocess.run([sys.executable, "-c", SCRIPT],
                          capture_output=True, text=True, timeout=1200,
                          env={**os.environ, "PYTHONPATH": "src",
                               "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")]
    assert line, proc.stdout
    res = json.loads(line[0][len("RESULT:"):])
    assert len(res) == 3
    for k, v in res.items():
        assert v["flops"] > 0, k


def test_mesh_factory_shapes():
    # pure metadata checks (no device allocation beyond host CPU)
    from repro.launch.mesh import make_production_mesh
    # cannot build 256-device mesh on 1 CPU: only verify the callable spec
    import inspect
    sig = inspect.signature(make_production_mesh)
    assert "multi_pod" in sig.parameters


def test_hlo_analyzer_on_synthetic_module():
    from repro.launch.hlo_analysis import analyze
    hlo = """
HloModule m

%body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,8]{1,0} get-tuple-element(%p), index=1
  %d = f32[8,8]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %c1 = s32[] constant(1)
  %ni = s32[] add(%i, %c1)
  ROOT %t = (s32[], f32[8,8]) tuple(%ni, %d)
}

%cond (p: (s32[], f32[8,8])) -> pred[] {
  %p = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(5)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (a: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8]{1,0} parameter(0)
  %z = s32[] constant(0)
  %t0 = (s32[], f32[8,8]) tuple(%z, %a)
  %w = (s32[], f32[8,8]) while(%t0), condition=%cond, body=%body
  ROOT %r = f32[8,8]{1,0} get-tuple-element(%w), index=1
}
"""
    a = analyze(hlo)
    # 5 iterations x 2*8*8*8 flops
    assert a["dot_flops"] == 5 * 2 * 8 * 8 * 8
