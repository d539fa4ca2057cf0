"""On-hardware Pallas kernel parity (north star: "CUDA kernels become
Pallas kernels").

These tests run the COMPILED kernels on a real TPU chip and check
numerics against the reference math — the proof the interpreter-mode
tests cannot give (Mosaic's alignment, layout and VMEM rules only apply
on real compiles; an earlier chunked_topk wrote one column per iteration
and passed interpreter tests while failing TPU compilation).

The suite conftest pins this process to the CPU mesh, so the kernels run
in ONE child process (tests/kernels_tpu_child.py) that uses the chip: a
chip belongs to one process, and one child pays one start-up. The module
skips only when the run is pinned to the CPU (``JAX_PLATFORMS=cpu``, as
in the sandbox and tier-1); anywhere else a missing chip is a failure.
Through the chip tool:

    chiprun -- python -m pytest tests/test_kernels_tpu.py -q

``KERNELS_TPU_JSON=<path>`` keeps the child's full record (every error
with the compiler's words) — the CHANGES.md kernel table is made from it.
"""

import json
import os
import subprocess
import sys

import pytest

pytestmark = [
    pytest.mark.tpu,
    pytest.mark.slow,
    pytest.mark.skipif(
        os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu",
        reason="run pinned to the CPU (JAX_PLATFORMS=cpu)",
    ),
]

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def chip():
    """The child's record: ``{"device": ..., "<group>": {...}}``."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests", "kernels_tpu_child.py")],
        capture_output=True, text=True, timeout=1500, cwd=REPO, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("{")][-1]
    if os.environ.get("KERNELS_TPU_JSON"):
        with open(os.environ["KERNELS_TPU_JSON"], "w") as f:
            f.write(line + "\n")
    result = json.loads(line)
    assert result["device"]["platform"] == "tpu", result["device"]
    return result


def _group(chip, name):
    g = chip[name]
    assert "error" not in g, g["error"]
    return g


def test_codec_kernels_match_reference_on_tpu(chip):
    g = _group(chip, "codec")
    assert all(g.values()), g


def test_fused_wire_kernels_bit_exact_on_tpu(chip):
    """PR 9's one-pass wire, compiled for the first time: per format, at
    bucket size and at the narrow chunk-128 geometry, the payload is the
    plain-ops path's bytes, the tracking update is exactly what a
    receiver decodes, and the fused receive matches."""
    for geom, r in _group(chip, "fused_wire").items():
        assert r["payload_exact"], (geom, r)
        assert r["xhat_tracks_decode"], (geom, r)
        assert r["decode_exact"], (geom, r)


def test_flash_attention_on_tpu(chip):
    g = _group(chip, "flash")
    for k, v in g.items():
        assert v < 0.02, (k, g)  # bf16-precision matmuls on both sides


def test_ring_flash_inside_shard_map_on_tpu(chip):
    """The compiled offset kernels under a checked shard_map — the
    production settings the CPU tests can only interpret."""
    g = _group(chip, "ring_flash")
    for k, v in g.items():
        if k != "devices":
            assert v < 0.02, (k, g)


@pytest.mark.xfail(
    strict=True,
    reason="refused by Mosaic on jax 0.9.0 / libtpu 0.0.34 (TPU v5 lite, "
    "PR 21): \"'tpu.matmul' op Not implemented: Up to 1 batch dim "
    "supported\" — the body's rank-4 einsum batches over (slot, head) "
    "with the head axis not leading. The kernel needs a redesign "
    "(ROADMAP A1); until then attn_impl='auto' raises this on a TPU and "
    "the engine default stays 'gather'. Strict: the day the kernel "
    "compiles this marker has to go.",
)
def test_fused_paged_attention_on_tpu(chip):
    """fused_paged_attn_w1 / w{k+1} at GPT-2-medium head shapes, on a
    pool the engine would really allocate and on a toy one."""
    for geom, r in _group(chip, "paged_attention").items():
        assert "error" not in r, (geom, r["error"])
        assert r["bit_exact"], (geom, r)


def test_fused_bn_on_tpu(chip):
    """The compiled fused-BN kernels match the jnp custom-VJP math on the
    chip (wide C>=128 and lane-packed C<128 variants, fwd + all grads)."""
    for name, e in _group(chip, "fused_bn").items():
        assert e["loss"] < 1e-2 and e["dx"] < 1e-4, (name, e)
        assert e["dgamma"] < 1e-2 and e["dbeta"] < 1e-2, (name, e)


def test_fused_ln_on_tpu(chip):
    """The compiled fused-LN kernel matches the jnp custom-VJP math on
    the chip at the transformer row shapes (fwd + all grads); proves the
    Mosaic compile the interpreter tests cannot (cross-lane row
    reductions + revisited accumulator blocks)."""
    for name, e in _group(chip, "fused_ln").items():
        assert e["loss"] < 2e-2 and e["dx"] < 1e-2, (name, e)
        assert e["dgamma"] < 5e-2 and e["dbeta"] < 5e-2, (name, e)


def test_ssd_scan_on_tpu(chip):
    """The fused SSD scan pair, compiled at the hybrid cell's shapes: values
    and all five gradients against the reference's token-by-token recurrence
    (bfloat16 operands against float32 at ``highest``) no further off than
    XLA's chunked scan is, and against that scan to bfloat16's rounding."""
    g = _group(chip, "ssd")
    for name in ("y", "dx", "ddt", "da", "db", "dc"):
        assert g[f"{name}_vs_recurrence"] < max(0.02, 1.5 * g[f"xla_{name}_vs_recurrence"]), (name, g)
        assert g[f"{name}_vs_xla"] < 0.02, (name, g)


def test_gated_delta_scan_on_tpu(chip):
    """The fused gated-delta-rule pair, compiled at the delta cell's shapes:
    values and all five gradients against the reference's token-by-token
    recurrence (bfloat16 operands against float32 at ``highest``) no further off
    than XLA's chunked rule is, and against that rule to bfloat16's rounding."""
    g = _group(chip, "gdn")
    for name in ("y", "dq", "dk", "dv", "dg", "dbeta"):
        assert g[f"{name}_vs_recurrence"] < max(0.02, 1.5 * g[f"xla_{name}_vs_recurrence"]), (name, g)
        assert g[f"{name}_vs_xla"] < 0.02, (name, g)


def test_flash_attention_at_latent_widths_on_tpu(chip):
    """Flash attention with 192-wide keys beside 128-wide values at the yarn
    scale, compiled at the latent cell's call: the output and all three
    gradients against the blockwise XLA path to bfloat16's rounding, with the
    192-wide blocks as they are (what the program runs) and zero-padded to 256."""
    g = _group(chip, "mla")
    for path in ("native", "padded"):
        for name in ("y", "dq", "dk", "dv"):
            assert g[f"{path}_{name}_vs_xla"] < 0.02, (path, name, g)


def test_hyper_connected_residual_kernels_on_tpu(chip):
    """The residual path's four kernels, compiled at the latent cell's streams
    (1 x 4 x 4,096 x 3584, bfloat16): the chooser takes them, ``u``, the maps and
    the stream sizes read as the plain XLA path to float32 round-off, ``X'`` and
    the bfloat16 cotangents to bfloat16's rounding, the parameters' gradients to
    float32's sums in another order, and no kernel moves its bytes slower than
    half the HBM peak (75-82% when they were written; XLA's fusions ran the path
    at 6%)."""
    g = _group(chip, "mhc")
    assert g["impl"] == "kernel", g
    for name in ("u", "h_res", "h_post", "stream_rms"):
        assert g[f"{name}_vs_xla"] < 1e-5, (name, g)
    for name in ("dphi", "dbias", "dgate"):
        assert g[f"{name}_vs_xla"] < 1e-4, (name, g)
    for name in ("x_out", "dx", "dy"):
        assert g[f"{name}_vs_xla"] < 0.02, (name, g)
    for name in ("mhc_read_fwd", "mhc_write_fwd", "mhc_write_bwd", "mhc_read_bwd"):
        assert g[name]["hbm_roofline_pct"] > 50.0, (name, g)


def test_the_allocator_keeps_a_running_programs_temporaries_out_of_bytes_in_use(chip):
    """What ``obs/memviz.py:HbmSample`` says of its fields (PR 35): while a program
    with 4.3 GB of temporaries runs, ``bytes_in_use`` and the lifetime
    ``peak_bytes_in_use`` read the arrays alone (1.5e-5 of the program's
    temporaries when it was written) and ``bytes_reserved`` reads the
    temporaries (0.99997): nothing before its first run, kept after it, ONE
    workspace for the programs that have run (the largest's: a 1.07 GB program
    beside it adds nothing) and the smaller one's once the larger is dropped.
    If this fails the runtime changed, and ``peak_hbm_pct.train``,
    ``round_hbm_pct.train`` and ``round_workspace_hbm_pct.train`` mean something
    else."""
    g = _group(chip, "hbm_sampler")
    big, small = g["big"], g["small"]
    assert big["work_bytes"] > 2 * 2**30 and big["second_run"]["run_s"] > 0.5
    assert big["second_run"]["largest_while_running"]["samples"] > 20
    assert abs(g["sampled_over_compiled"]) < 0.1 and abs(g["peak_over_compiled"]) < 0.1, g
    assert 0.9 < g["reserved_over_compiled"] < 1.1, g
    held = big["second_run"]["after"]["bytes_reserved"]
    assert big["once_built"]["bytes_reserved"] < 0.01 * held  # reserved at the first run, not when built
    assert small["first_run"]["largest_while_running"]["bytes_reserved"] == held  # shared, not summed
    assert 0.9 < small["once_the_big_program_was_dropped"]["bytes_reserved"] / small["work_bytes"] < 1.1
