"""The one way into a Pallas kernel (``ops/pallas/spmd.py plan`` /
``over_batch``, PR 56): under every kind of mesh, with the family's guard
refusing or not, for a family that takes a ``shard`` verdict and one that
does not, the verdict returned, the ONE ``kernel_dispatch_total`` row
booked, and the ``shard_map`` specs of split and whole arguments.  Nothing
runs on a device: ``plan`` decides in Python and ``over_batch`` is traced.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.comm import mesh as mesh_lib
from deepspeed_tpu.ops import attention
from deepspeed_tpu.ops.pallas import spmd

SITE = "test_spmd"
REFUSED = "kernel_mesh_plan refused the mesh"
# mesh -> (axes or None for no mesh, devices of the process without a mesh,
# rows of the batch, kernel_mesh_plan's verdict)
MESHES = {
    "no_mesh_one_device": (None, 1, 4, ("direct", None)),
    "no_mesh_several": (None, 8, 4, (None, None)),
    "one_device_mesh": ({"dp": 1}, 8, 4, ("direct", None)),
    "dp": ({"dp": 8}, 8, 8, ("shard", ("dp",))),
    "fsdp": ({"fsdp": 4, "dp": 2}, 8, 8, ("shard", ("dp", "fsdp"))),
    "tp": ({"tp": 2, "dp": 4}, 8, 8, (None, None)),
    "sp": ({"sp": 2, "dp": 4}, 8, 8, (None, None)),
    "pp": ({"pp": 2, "dp": 4}, 8, 8, (None, None)),
    "rows_not_divided": ({"dp": 8}, 8, 6, (None, None)),
}


@pytest.fixture
def under(monkeypatch):
    """Sets a case's mesh (and what the process says of its devices) and a
    TPU; restores the mesh."""
    prev = mesh_lib.get_mesh(required=False)
    monkeypatch.setattr(attention, "on_tpu", lambda: True)

    def set_(mesh):
        axes, devices, rows, verdict = MESHES[mesh]
        monkeypatch.setattr(jax, "device_count", lambda: devices)
        n = 1 if axes == {"dp": 1} else 8
        mesh_lib.set_mesh(None if axes is None else mesh_lib.build_mesh(
            axes, devices=jax.devices()[:n]))
        return rows, verdict

    yield set_
    mesh_lib.set_mesh(prev)


def _booked(call):
    """``call()`` and the ``(impl, reason, count)`` rows it added under
    :data:`SITE`."""
    before = {r[:3]: r[3] for r in spmd.dispatch_report()}
    out = call()
    return out, [(r[1], r[2], r[3] - before.get(r[:3], 0))
                 for r in spmd.dispatch_report()
                 if r[0] == SITE and r[3] > before.get(r[:3], 0)]


@pytest.mark.parametrize("takes_shard", [True, False],
                         ids=["takes_shard", "one_device_only"])
@pytest.mark.parametrize("guard", [None, "rows of 100 are no whole tiles"],
                         ids=["guard_passed", "guard_refused"])
@pytest.mark.parametrize("mesh", sorted(MESHES))
def test_plan_gives_the_verdict_and_books_one_row(under, mesh, guard,
                                                  takes_shard):
    rows, (verdict, axes) = under(mesh)
    plan, booked = _booked(lambda: spmd.plan(
        SITE, rows, guard, "tiles of 128", shard=takes_shard))
    if guard is not None:
        want, row = None, ("xla", guard)
    elif verdict is None:
        want, row = None, ("xla", REFUSED)
    elif verdict == "shard" and not takes_shard:
        want, row = None, ("xla", "the kernels take one device's own "
                           f"operands, the mesh a shard_map over batch axes "
                           f"{axes}")
    else:
        want = (verdict, axes)
        row = ("pallas", "tiles of 128; " + (
            "one device" if verdict == "direct"
            else f"shard_map over batch axes {axes}"))
    assert plan == want
    assert booked == [(*row, 1)]


@pytest.mark.parametrize("case,kw,tpu,want,row", [
    # the family's own labels
    ("labels", dict(fallback="ragged_dot", kernel="megablox"), True,
     ("direct", None), ("megablox", "tiles of 128; one device")),
    # no TPU: asked once, here, after the family's guard
    ("no_tpu", dict(), False, None, ("xla", "no TPU")),
    ("no_tpu_after_the_guard", dict(refusal="float32 rows"), False, None,
     ("xla", "float32 rows")),
    # the interpreter, or a caller that has asked: no TPU wanted
    ("tpu_blind", dict(tpu=False), False, ("direct", None),
     ("pallas", "tiles of 128; one device")),
    # a caller inside a shard_map says so: the mesh is not asked
    ("callers_own_rows", dict(batch=None), True, ("direct", None),
     ("pallas", "tiles of 128")),
])
def test_plan_asks_the_device_and_the_mesh_once(under, case, kw, tpu, want,
                                                row, monkeypatch):
    under("one_device_mesh" if case != "callers_own_rows" else "tp")
    monkeypatch.setattr(attention, "on_tpu", lambda: tpu)
    kw = dict(dict(batch=4, refusal=None), **kw)
    plan, booked = _booked(lambda: spmd.plan(
        SITE, kw.pop("batch"), kw.pop("refusal"), "tiles of 128", **kw))
    assert plan == want and booked == [(*row, 1)]


@pytest.mark.parametrize("mesh,guard,reason", [
    ("tp", None, REFUSED),
    ("one_device_mesh", "rows of 100 are no whole tiles",
     "rows of 100 are no whole tiles")])
def test_kernels_asked_for_by_name_raise_where_refused(under, mesh, guard,
                                                       reason):
    rows, _ = under(mesh)

    def asked():
        with pytest.raises(NotImplementedError,
                           match=f"{SITE} impl='pallas': {reason}"):
            spmd.plan(SITE, rows, guard, "tiles of 128", must=True)

    assert _booked(asked)[1] == []      # nothing ran, nothing is booked


@pytest.mark.parametrize("case,shapes,kw,ins,outs", [
    # rows and taps: short_conv
    ("rows_and_taps", [(8, 16, 32), (32, 3)], dict(whole=(1,)),
     [("fsdp", None, None), ()], [("fsdp", None, None)]),
    # five rows: the gated delta rule
    ("all_rows", [(8, 16, 32)] * 3 + [(8, 16, 4)] * 2, dict(),
     [("fsdp", None, None)] * 5, [("fsdp", None, None)]),
    # q, k, a table a row, scales absent: rotate_rows
    ("table_a_row", [(8, 16, 64), (8, 16, 32), (8, 16, 128), None, None],
     dict(outs=2, whole=(3, 4)),
     [("fsdp", None, None)] * 3 + [None, None], [("fsdp", None, None)] * 2),
    # one (1, S) table for every row, and scales
    ("one_table", [(8, 16, 64), (8, 16, 32), (1, 16, 128), (128,), (128,)],
     dict(outs=2, whole=(2, 3, 4)),
     [("fsdp", None, None)] * 2 + [(), (), ()],
     [("fsdp", None, None)] * 2),
    # 2-D rows beside a 3-D expert leaf
    ("two_d_rows", [(8, 32), (4, 32, 32)], dict(whole=(1,)),
     [("fsdp", None), ()], [("fsdp", None)]),
    # three results: slot_rows
    ("three_results", [(8, 16, 96)], dict(outs=3),
     [("fsdp", None, None)], [("fsdp", None, None)] * 3),
])
def test_over_batch_splits_rows_and_leaves_the_rest_whole(under, case, shapes,
                                                          kw, ins, outs):
    under("fsdp")
    args = tuple(None if s is None else jax.ShapeDtypeStruct(s, jnp.float32)
                 for s in shapes)
    present = [a for a in args if a is not None]

    def specs(plan):        # make_jaxpr takes no None: the absent stay out
        def call(*given):
            it = iter(given)
            return spmd.over_batch(
                lambda *a: (a[0] * 2,) * kw.get("outs", 1)
                if kw.get("outs", 1) > 1 else a[0] * 2, plan,
                tuple(None if a is None else next(it) for a in args), **kw)
        jaxpr = jax.make_jaxpr(call)(*present)
        maps = [e for e in jaxpr.jaxpr.eqns
                if e.primitive.name == "shard_map"]
        if not maps:
            return None
        return (tuple(maps[0].params["in_specs"]),
                tuple(maps[0].params["out_specs"]))

    assert specs(("direct", None)) is None
    got_in, got_out = specs(("shard", ("fsdp",)))
    assert got_in == tuple(P(*s) for s in ins if s is not None)
    assert got_out == tuple(P(*s) for s in outs)


def test_a_shard_verdict_without_batch_axes_splits_nothing(under):
    """``kernel_mesh_plan`` may say ``shard`` over no axis (every batch axis
    of size one on a mesh of several devices): the kernel still runs inside
    a full-manual ``shard_map``, on whole operands."""
    under("fsdp")
    x = jax.ShapeDtypeStruct((8, 16, 32), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda x: spmd.over_batch(
        lambda x: x * 2, ("shard", ()), (x,)))(x)
    (eqn,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "shard_map"]
    assert tuple(eqn.params["in_specs"]) == (P(None, None, None),)
