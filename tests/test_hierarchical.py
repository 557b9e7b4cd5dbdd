"""Two-level ICI x DCN exchange (PR 11 tentpole).

Contracts under test, on a (2, 4) remesh of the 8-device CPU harness:

* per-leg error feedback: an EF codec on the DCN hop conserves mass
  exactly -- the new residual is the DCN-leg operand with the sent
  coordinates zeroed, and (sent + held) equals the pre-exchange total;
* degenerate topology: at ``dcn_size=1`` the op statically falls back to
  the flat psum and is BITWISE identical to :func:`allreduce`;
* elastic resize across a slice boundary: the two-level mesh re-derives
  from the topology spec, and ``ef_resize_residuals`` carries the
  ``[world, 2, shard]`` per-leg residuals when the ICI extent survives
  the resize -- and zeroes them (counted) when the shard width changes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hv
from horovod_tpu.collectives import ops as _ops
from horovod_tpu.collectives.compression import (parse_compression,
                                                 topk_count)
from horovod_tpu.core.state import global_state
from horovod_tpu.optim import distributed as _dist
from horovod_tpu.parallel.mesh import build_mesh, parse_topology_spec


def _two_level(dcn_size):
    """Re-init the framework on a (dcn_size, 8/dcn_size) mesh."""
    import horovod_tpu as hvd_mod
    hvd_mod.shutdown()
    hvd_mod.init(mesh=build_mesh(jax.devices()[:8], hierarchical=True,
                                 dcn_size=dcn_size))
    return hvd_mod


@pytest.fixture()
def hier():
    """(dcn, ici) = (2, 4): two slices of four chips."""
    hvd_mod = _two_level(2)
    yield hvd_mod
    hvd_mod.shutdown()


@pytest.fixture()
def hier_single_slice():
    """(dcn, ici) = (1, 8): the degenerate single-slice topology."""
    hvd_mod = _two_level(1)
    yield hvd_mod
    hvd_mod.shutdown()


def _shard_run(fn, *arrays):
    """Run ``fn(per_rank_rows...)`` under shard_map, the leading axis
    sharded jointly over both mesh axes (dcn-major rank order)."""
    mesh = global_state().mesh
    spec = P(tuple(mesh.axis_names))

    def spmd(*blocks):
        out = fn(*[b[0] for b in blocks])
        return jax.tree.map(lambda y: y[None], out)

    return jax.jit(jax.shard_map(spmd, mesh=mesh, in_specs=spec,
                                 out_specs=spec))(*arrays)


# ---------------------------------------------------------------------------
# Per-leg error feedback.
# ---------------------------------------------------------------------------

def test_hier_ef_dcn_leg_conserves_mass_exactly(hier):
    """topk on the DCN hop: each rank's new residual is EXACTLY the
    DCN-leg operand (ICI-reduced shard + re-injected residual) with the
    k kept coordinates zeroed, and the slice-leader exchange receives
    precisely the sent mass -- nothing is lost between the legs.

    Integer-valued inputs keep every sum exact, so the assertions are
    equality, not tolerance."""
    n_dcn, n_ici, world = 2, 4, 8
    size = 256                      # == lcm(256, 4): no padding tail
    shard = size // n_ici
    fraction = 0.25
    rng = np.random.RandomState(0)
    x = rng.randint(-8, 9, (world, size)).astype(np.float32)
    # Choose residuals so the DCN-leg operand v has DISTINCT integer
    # magnitudes per rank (unambiguous top-k): v = slice_sum + res_in.
    xs = x.reshape(n_dcn, n_ici, size)
    slice_sum = xs.sum(axis=1)      # per-slice ICI reduction
    v = np.stack([
        (rng.permutation(shard) + 1.0)
        * rng.choice([-1.0, 1.0], shard)
        for _ in range(world)]).astype(np.float32)
    res_in = np.stack([
        v[d * n_ici + i] - slice_sum[d, i * shard:(i + 1) * shard]
        for d in range(n_dcn) for i in range(n_ici)]).astype(np.float32)
    comp = parse_compression(f"topk:{fraction}")

    def f(row, res):
        return _ops.hierarchical_allreduce(
            row, hv.Sum, dcn_axis="dcn", ici_axis="ici",
            dcn_codec=comp, dcn_residual=res)

    out, res_new = _shard_run(f, x, res_in)
    out, res_new = np.asarray(out), np.asarray(res_new)
    k = topk_count(shard, fraction)
    assert 0 < k < shard
    # Per-rank EF contract: residual == v with the k largest-|v| coords
    # zeroed; sent (= v - residual) is k-sparse.
    for r in range(world):
        keep = np.argsort(np.abs(v[r]))[-k:]
        expect = v[r].copy()
        expect[keep] = 0.0
        np.testing.assert_array_equal(res_new[r], expect)
        assert np.count_nonzero(v[r] - res_new[r]) == k
    # Cross-slice conservation per ICI position: the exchanged shard
    # equals the sum of what the slices sent, so sent + held == total
    # pre-exchange mass with zero leakage.
    for i in range(n_ici):
        ranks = [d * n_ici + i for d in range(n_dcn)]
        sent_sum = sum(v[r] - res_new[r] for r in ranks)
        got = out[ranks[0]][i * shard:(i + 1) * shard]
        np.testing.assert_array_equal(got, sent_sum)
        # ...and every rank allgathered the same result.
        for r in range(1, world):
            np.testing.assert_array_equal(
                out[r][i * shard:(i + 1) * shard], got)


# ---------------------------------------------------------------------------
# Degenerate topology.
# ---------------------------------------------------------------------------

def test_hier_single_slice_is_bitwise_flat(hier_single_slice):
    """dcn_size=1: the two-level op statically falls back to the flat
    psum over both axes -- bitwise identical outputs, not just close."""
    world = 8
    x = np.random.RandomState(1).randn(world, 300).astype(np.float32)

    def f(row):
        h = _ops.hierarchical_allreduce(row, hv.Average, dcn_axis="dcn",
                                        ici_axis="ici")
        flat = _ops.allreduce(row, hv.Average, axes=("dcn", "ici"))
        return h, flat

    h, flat = _shard_run(f, x)
    np.testing.assert_array_equal(np.asarray(h), np.asarray(flat))


def test_hier_single_slice_ef_passes_residual_through(hier_single_slice):
    """dcn_size=1 with an EF DCN codec: nothing crosses DCN, so the
    residual must ride through untouched (no mass invented or lost)."""
    world = 8
    x = np.random.RandomState(2).randn(world, 256).astype(np.float32)
    shard = 256 // 8
    res_in = np.random.RandomState(3).randn(world, shard) \
        .astype(np.float32)
    comp = parse_compression("topk:0.25")

    def f(row, res):
        return _ops.hierarchical_allreduce(
            row, hv.Sum, dcn_axis="dcn", ici_axis="ici",
            dcn_codec=comp, dcn_residual=res)

    _, res_new = _shard_run(f, x, res_in)
    np.testing.assert_array_equal(np.asarray(res_new), res_in)


# ---------------------------------------------------------------------------
# Elastic resize across a slice boundary.
# ---------------------------------------------------------------------------

def test_elastic_resize_across_slice_boundary_carries_residuals(hier):
    """Losing a slice (2x4 -> 1x4): the surviving topology re-derives
    from the explicit spec, and because the ICI extent -- hence the
    per-leg shard width -- survives, ``ef_resize_residuals`` carries the
    dropped slice's pending DCN mass instead of zeroing it."""
    comp = parse_compression("ici:none,dcn:topk:0.25")
    params = {"w": jnp.zeros((300,), jnp.float32),
              "b": jnp.zeros((40,), jnp.float32)}
    res = _dist.ef_init_residuals(params, None, comp)
    # Per-leg residual rows are [world, 2, shard]: 340 elements pad to
    # 512 (quantum lcm(256, 4)), shard 512/4 = 128.
    assert [tuple(r.shape) for r in res] == [(8, 2, 128)]
    res = tuple(
        jnp.arange(r.size, dtype=jnp.float32).reshape(r.shape) + 1.0
        for r in res)
    old_mass = [np.asarray(r).sum(axis=0) / 8 for r in res]

    hierarchical, dcn_size = parse_topology_spec("1,4", n=4)
    assert hierarchical and dcn_size == 1
    hv.shutdown()
    hv.init(mesh=build_mesh(jax.devices()[:4], hierarchical=True,
                            dcn_size=dcn_size))
    assert tuple(global_state().mesh.shape.values()) == (1, 4)

    new_res, report = _dist.ef_resize_residuals(res, params, 8, 4,
                                                compression=comp)
    assert report["zeroed_buckets"] == 0
    assert report["carried_bytes"] > 0
    assert [tuple(r.shape) for r in new_res] == [(4, 2, 128)]
    # The exchange averages over world: sum(res')/new == sum(res)/old,
    # so the dropped slice's pending correction mass is preserved.
    for old, new in zip(old_mass, new_res):
        np.testing.assert_allclose(np.asarray(new).sum(axis=0) / 4, old,
                                   rtol=1e-6)


def test_elastic_resize_changing_ici_extent_zeroes_counted(hier):
    """A resize that changes the ICI extent (2x4 -> 2x2) changes the
    shard width: the per-leg residual layout is irreconcilable, so the
    carry must be ZEROED with the zeroing counted -- never silently
    misaligned into the wrong coordinates."""
    comp = parse_compression("ici:none,dcn:topk:0.25")
    params = {"w": jnp.zeros((300,), jnp.float32),
              "b": jnp.zeros((40,), jnp.float32)}
    res = _dist.ef_init_residuals(params, None, comp)
    res = tuple(jnp.ones(r.shape, jnp.float32) for r in res)

    hv.shutdown()
    hv.init(mesh=build_mesh(jax.devices()[:4], hierarchical=True,
                            dcn_size=2))
    new_res, report = _dist.ef_resize_residuals(res, params, 8, 4,
                                                compression=comp)
    assert report["zeroed_buckets"] == len(res) == 1
    # New layout: 340 pads to 512 (quantum lcm(256, 2)), shard 512/2.
    assert [tuple(r.shape) for r in new_res] == [(4, 2, 256)]
    assert all(float(jnp.abs(r).max()) == 0.0 for r in new_res)


# ---------------------------------------------------------------------------
# The leg plan's closed form.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("compression, dcn_bytes_per_element", [
    (None, 4.0), ("fp16", 2.0), ("ici:none,dcn:topk:0.01", 0.04)])
def test_hier_plan_dcn_leg_undercuts_flat_and_is_mesh_invariant(
        compression, dcn_bytes_per_element):
    """What the two-level decomposition is for, from the plan alone: the
    DCN hop carries 1/n_ici of the bucket (times its codec's ratio), well
    under the flat all-reduce's bytes; the per-leg bytes do not depend on
    how many slices there are, and the padded bucket is the same for
    every ICI extent that divides 256."""
    from horovod_tpu.controller.fusion import plan_hier_legs
    size = 1_000_003                    # odd on purpose: the pad shows
    flat_bytes = size * 4

    def legs(n_dcn, n_ici):
        return {l.tag: l for l in plan_hier_legs(
            size, "float32", n_dcn=n_dcn, n_ici=n_ici,
            compression=compression)}

    base = legs(2, 8)
    assert set(base) == {"hier/ici_rs", "hier/dcn_ar", "hier/ici_ag"}
    padded = base["hier/ici_rs"].elements
    assert size <= padded < size + 256 and padded % 256 == 0
    dcn = base["hier/dcn_ar"]
    assert dcn.elements == padded // 8
    assert dcn.nbytes == pytest.approx(
        dcn.elements * dcn_bytes_per_element, rel=1e-3)
    assert 0 < dcn.nbytes < flat_bytes / 8 + 256 * 4
    for n_dcn in (4, 32):               # more slices: the same legs
        other = legs(n_dcn, 8)
        assert {t: (l.nbytes, l.elements) for t, l in other.items()} == \
            {t: (l.nbytes, l.elements) for t, l in base.items()}
    assert legs(2, 4)["hier/ici_rs"].elements == padded
