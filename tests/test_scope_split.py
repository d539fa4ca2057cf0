"""tools/scope_split.py groups a capture's device time by the scope its
operations were traced in (PERF.md section 5's table rests on it): leaf
operations only, the first of ``SCOPES`` a scope names, ``bwd`` under a
transpose, ``no_scope`` without one. The capture here is hand-made."""

import importlib.util
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PS_A_MS = 10**9


@pytest.fixture(scope="module")
def scope_split():
    spec = importlib.util.spec_from_file_location(
        "scope_split", os.path.join(REPO, "tools", "scope_split.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _space():
    xplane_pb2 = pytest.importorskip("tensorflow.tsl.profiler.protobuf.xplane_pb2")
    space = xplane_pb2.XSpace()
    host = space.planes.add(name="/host:CPU")  # never read: not a device plane
    host.event_metadata[1].name = "%fusion.1"
    host.lines.add(name="XLA Ops").events.add(metadata_id=1, offset_ps=0, duration_ps=99 * PS_A_MS)

    dev = space.planes.add(name="/device:TPU:0")
    dev.stat_metadata[1].name = "tf_op"
    grad = "jit(train_step)/while/body/train.grad"
    # a scope is the stat's own string, or a reference to a stat's name
    dev.stat_metadata[2].name = f"{grad}/transpose(jvp(Model))/h_0/mixer/ssm.scan/mul"
    operations = {
        1: ("%while.5 = (f32[]) while(%tuple.1)", "jit(train_step)/while"),
        2: ("%fusion.3 = bf16[8,128] fusion(%p.1)", f"{grad}/jvp(Model)/h_1/mixer/moe.sort/argsort"),
        # the kernel's own scope sits inside moe.sort: the first of SCOPES wins
        3: ("%moe_rows_gather.7 = bf16[8,128] custom-call(%p.2)",
            f"{grad}/jvp(Model)/h_1/mixer/moe.sort/moe_rows_gather/pallas_call"),
        4: ("%fusion.12 = f32[8,128] fusion(%p.3)", None),  # scope by reference, below
        5: ("%copy.2 = f32[8,128] copy(%p.4)", f"{grad}/transpose(jvp(Model))/h_3/norm/mul"),
        6: ("%copy.9 = f32[8] copy(%p.5)", ""),
        7: ("%jit_train_step.1", ""),
    }
    for mid, (name, scope) in operations.items():
        md = dev.event_metadata[mid]
        md.name = name
        if scope is None:
            md.stats.add(metadata_id=1, ref_value=2)
        elif scope:
            md.stats.add(metadata_id=1, str_value=scope)
    modules = dev.lines.add(name="XLA Modules")
    for start, ms in ((0, 20), (30, 24)):
        modules.events.add(metadata_id=7, offset_ps=start * PS_A_MS, duration_ps=ms * PS_A_MS)
    ops = dev.lines.add(name="XLA Ops")
    # (operation, start ms, duration ms): the while holds the four that follow it
    for mid, start, ms in ((1, 0, 18), (2, 1, 2), (3, 3, 4), (4, 8, 6), (5, 14, 3), (6, 19, 1),
                           (1, 30, 20), (2, 31, 2), (4, 34, 10)):
        ops.events.add(metadata_id=mid, offset_ps=start * PS_A_MS, duration_ps=ms * PS_A_MS)
    return space


def test_split_space_groups_leaf_operations_by_scope(scope_split):
    out = scope_split.split_space(_space(), {"tag": "t"})
    assert out["tag"] == "t" and out["rounds"] == 2
    assert out["round_ms_device"] == 24.0  # the upper median of the two programs
    # milliseconds a round (two rounds); the whiles' 38 ms are their bodies'
    assert out["ms_a_round_by_scope"] == {
        "ssm.scan": 8.0, "moe.sort": 2.0, "moe_rows_gather": 2.0, "block_other": 1.5, "no_scope": 0.5,
    }
    assert list(out["ms_a_round_by_scope"]) == ["ssm.scan", "moe.sort", "moe_rows_gather", "block_other", "no_scope"]
    assert out["ms_a_round_by_scope_dir"] == {
        "ssm.scan|bwd": 8.0, "moe.sort|fwd": 2.0, "moe_rows_gather|fwd": 2.0, "block_other|bwd": 1.5,
        "no_scope|fwd": 0.5,
    }
    # the instruction's name without its number
    assert out["ms_a_round_top_ops"] == {
        "ssm.scan|fusion": 8.0, "moe.sort|fusion": 2.0, "moe_rows_gather|moe_rows_gather": 2.0,
        "block_other|copy": 1.5, "no_scope|copy": 0.5,
    }


def test_split_space_without_a_device_plane_adds_nothing(scope_split):
    space = _space()
    del space.planes[1]
    assert scope_split.split_space(space, {"tag": "t"}) == {"tag": "t"}
