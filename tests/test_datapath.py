"""The paper's special-case suite: 20 hand-constructed ray/box/triangle
cases exercising the edge behaviour the RTL is designed for (§I: "twenty
special ray-box/ray-triangle test cases"), plus Table VII stage semantics."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (Box, Ray, Triangle, make_ray, quadsort,
                        ray_box_test, ray_triangle_test)


def ray(o, d, extent=None):
    return make_ray(jnp.asarray([o], jnp.float32), jnp.asarray([d], jnp.float32),
                    None if extent is None else jnp.asarray([extent]))


def boxes4(*lohi):
    lo = jnp.asarray([[b[0] for b in lohi]], jnp.float32)
    hi = jnp.asarray([[b[1] for b in lohi]], jnp.float32)
    return Box(lo=lo, hi=hi)


UNIT = ((0, 0, 0), (1, 1, 1))


def unit4():
    return boxes4(UNIT, UNIT, UNIT, UNIT)


def tri(a, b, c):
    return Triangle(a=jnp.asarray([a], jnp.float32),
                    b=jnp.asarray([b], jnp.float32),
                    c=jnp.asarray([c], jnp.float32))


# ---- ray-box special cases (tavianator boundary semantics) -----------------


def test_case01_hit_through_center():
    qb = ray_box_test(ray((-1, .5, .5), (1, 0, 0)), unit4())
    assert bool(qb.is_intersect[0, 0]) and np.isclose(qb.tmin[0, 0], 1.0)


def test_case02_miss_parallel_outside():
    """Parallel to a slab, origin outside it: 0*inf NaN must not leak."""
    qb = ray_box_test(ray((-1, 2.0, .5), (1, 0, 0)), unit4())
    assert not np.asarray(qb.is_intersect).any()


def test_case03_parallel_on_boundary():
    """Ray gliding exactly on the box surface counts as hit (boundary
    convention of the branchless algorithm with comparator NaN-dropping)."""
    qb = ray_box_test(ray((-1, 0.0, .5), (1, 0, 0)), unit4())
    assert bool(qb.is_intersect[0, 0])


def test_case04_origin_inside():
    qb = ray_box_test(ray((.5, .5, .5), (1, 0, 0)), unit4())
    assert bool(qb.is_intersect[0, 0]) and np.isclose(qb.tmin[0, 0], 0.0)


def test_case05_box_behind():
    qb = ray_box_test(ray((2, .5, .5), (1, 0, 0)), unit4())
    assert not np.asarray(qb.is_intersect).any()


def test_case06_negative_direction():
    qb = ray_box_test(ray((2, .5, .5), (-1, 0, 0)), unit4())
    assert bool(qb.is_intersect[0, 0]) and np.isclose(qb.tmin[0, 0], 1.0)


def test_case07_negative_zero_direction():
    """dir = -0.0: the sign-bit swap must treat it as negative (inv = -inf)."""
    qb = ray_box_test(ray((.5, .5, .5), (-0.0, 1, 0)), unit4())
    assert bool(qb.is_intersect[0, 0])


def test_case08_diagonal_corner_hit():
    qb = ray_box_test(ray((-1, -1, -1), (1, 1, 1)), unit4())
    assert bool(qb.is_intersect[0, 0]) and np.isclose(qb.tmin[0, 0], 1.0)


def test_case09_degenerate_flat_box():
    """Zero-thickness box (lo == hi plane) still hits: boundary rule."""
    flat = ((0, 0, 0), (1, 1, 0))
    qb = ray_box_test(ray((.5, .5, -1), (0, 0, 1)), boxes4(flat, flat, flat, flat))
    assert bool(qb.is_intersect[0, 0])


def test_case10_sorted_output_with_indices():
    """Four boxes at different distances: outputs sorted, indices correct."""
    bx = boxes4(((3, 0, 0), (4, 1, 1)), ((1, 0, 0), (2, 1, 1)),
                ((7, 0, 0), (8, 1, 1)), ((5, 0, 0), (6, 1, 1)))
    qb = ray_box_test(ray((0, .5, .5), (1, 0, 0)), bx)
    assert np.asarray(qb.tmin[0]).tolist() == [1.0, 3.0, 5.0, 7.0]
    assert np.asarray(qb.box_index[0]).tolist() == [1, 0, 3, 2]
    assert np.asarray(qb.is_intersect[0]).all()


def test_case11_mixed_hit_miss_sorted():
    bx = boxes4(((3, 0, 0), (4, 1, 1)), ((1, 5, 0), (2, 6, 1)),  # box1 misses
                ((1, 0, 0), (2, 1, 1)), ((5, 5, 5), (6, 6, 6)))  # box3 misses
    qb = ray_box_test(ray((0, .5, .5), (1, 0, 0)), bx)
    hits = np.asarray(qb.is_intersect[0])
    tmin = np.asarray(qb.tmin[0])
    idx = np.asarray(qb.box_index[0])
    assert hits.sum() == 2
    hit_pairs = sorted((tmin[i], idx[i]) for i in range(4) if hits[i])
    assert hit_pairs == [(1.0, 2), (3.0, 0)]


# ---- ray-triangle special cases (Woop watertight, culling variant) ---------


def test_case12_front_face_hit():
    t = tri((0, 0, 1), (0, 1, 1), (1, 0, 1))
    r = ray((0.2, 0.2, 0), (0, 0, 1))
    out = ray_triangle_test(r, t)
    assert bool(out.hit[0])
    assert np.isclose(out.t_num[0] / out.t_denom[0], 1.0)


def test_case13_backface_culled():
    t = tri((0, 0, 1), (1, 0, 1), (0, 1, 1))  # reversed winding
    out = ray_triangle_test(ray((0.2, 0.2, 0), (0, 0, 1)), t)
    assert not bool(out.hit[0])


def test_case14_behind_origin():
    t = tri((0, 0, -1), (0, 1, -1), (1, 0, -1))
    out = ray_triangle_test(ray((0.2, 0.2, 0), (0, 0, 1)), t)
    assert not bool(out.hit[0])  # t_num < 0


def test_case15_edge_hit_watertight():
    """Hit exactly on a shared edge: U==0 boundary must count (>=0)."""
    t = tri((0, 0, 1), (0, 1, 1), (1, 0, 1))
    out = ray_triangle_test(ray((0.0, 0.5, 0), (0, 0, 1)), t)
    assert bool(out.hit[0])


def test_case16_vertex_hit_watertight():
    t = tri((0, 0, 1), (0, 1, 1), (1, 0, 1))
    out = ray_triangle_test(ray((0.0, 0.0, 0), (0, 0, 1)), t)
    assert bool(out.hit[0])


def test_case17_just_outside_edge():
    t = tri((0, 0, 1), (0, 1, 1), (1, 0, 1))
    out = ray_triangle_test(ray((-1e-4, 0.5, 0), (0, 0, 1)), t)
    assert not bool(out.hit[0])


def test_case18_degenerate_triangle_line():
    """Degenerate (zero-area) triangle: t_denom == 0 must not hit."""
    t = tri((0, 0, 1), (1, 0, 1), (2, 0, 1))
    out = ray_triangle_test(ray((0.5, 0.0, 0), (0, 0, 1)), t)
    assert not bool(out.hit[0])


def test_case19_oblique_direction_axis_permutation():
    """Dominant axis = y: exercises the kx/ky/kz permutation + shear."""
    t = tri((0, 2, 0), (1, 2, 0), (0, 2, 1))
    out = ray_triangle_test(ray((0.2, 0, 0.2), (0.1, 1, 0.05)), t)
    assert bool(out.hit[0])
    tt = float(out.t_num[0] / out.t_denom[0])
    assert 1.9 < tt * 1.0 < 2.2  # t ~ 2 along unnormalized dir


def test_case20_negative_dominant_axis():
    """dir[kz] < 0 triggers the kx/ky swap: winding must be preserved.

    Viewed along -z the (0,0)(1,0)(0,1) layout is the front-facing winding
    (mirror of test_case12's +z layout); the swapped-axes path must hit it
    and cull the reverse."""
    t = tri((0, 0, -1), (1, 0, -1), (0, 1, -1))
    out = ray_triangle_test(ray((0.2, 0.2, 0), (0, 0, -1)), t)
    assert bool(out.hit[0])
    assert np.isclose(out.t_num[0] / out.t_denom[0], 1.0)
    t_back = tri((0, 0, -1), (0, 1, -1), (1, 0, -1))
    out_b = ray_triangle_test(ray((0.2, 0.2, 0), (0, 0, -1)), t_back)
    assert not bool(out_b.hit[0])


# ---- stage primitives -------------------------------------------------------


def test_quadsort_all_permutations():
    """The 5-CAS network sorts all 24 permutations of distinct keys and
    carries payloads along."""
    import itertools
    for perm in itertools.permutations([0., 1., 2., 3.]):
        keys = jnp.asarray([perm])
        idx = jnp.asarray([[0, 1, 2, 3]], jnp.int32)
        sk, si = quadsort(keys, idx)
        assert np.asarray(sk[0]).tolist() == [0., 1., 2., 3.]
        assert [perm[i] for i in np.asarray(si[0])] == [0., 1., 2., 3.]


def test_quadsort_with_inf_and_ties():
    keys = jnp.asarray([[jnp.inf, 1.0, 1.0, -jnp.inf]])
    sk, = quadsort(keys)
    out = np.asarray(sk[0])
    assert out[0] == -np.inf and out[3] == np.inf and out[1] == out[2] == 1.0


def test_extent_not_applied_inside_datapath():
    """Table V: the datapath outputs tmin; extent filtering is external."""
    qb = ray_box_test(ray((-10, .5, .5), (1, 0, 0), extent=1.0), unit4())
    # still reports the geometric intersection at t=10
    assert bool(qb.is_intersect[0, 0]) and np.isclose(qb.tmin[0, 0], 10.0)


# ---- the sheared-axis pick of OpTriangle -------------------------------------

#: every class of f32: NaN, +-inf, +-0.0, subnormals, normals of both signs
SPECIAL = np.array([np.nan, np.inf, -np.inf, 0.0, -0.0, 1e-45, -1e-45,
                    1.1754942e-38, -3e-39, 1.0, -2.5, 3.4e38],
                   np.float32)


def _gather_dim(v, k):
    """The per-lane gather the select replaced, kept as the reference."""
    return jnp.take_along_axis(v, k[..., None], axis=-1)[..., 0]


def _bits(x):
    return np.asarray(x).view(np.uint32)


def _lanes(rays, width):
    """An (R,) ray batch shared across ``width`` lanes, as the wavefront
    hands it to OpTriangle: (R, width)."""
    return Ray(*[jnp.broadcast_to(f[:, None], (f.shape[0], width) + f.shape[1:])
                 for f in rays])


@pytest.mark.parametrize("k", [0, 1, 2])
def test_pick_axis_bitmatches_take_along_axis(k):
    """The select returns one operand unchanged: every bit pattern of the
    picked axis comes through, whatever the other two axes hold."""
    from repro.core.datapath import _pick_axis

    n = SPECIAL.size
    v = np.stack(np.meshgrid(SPECIAL, SPECIAL, SPECIAL, indexing="ij"),
                 axis=-1).reshape(n ** 3, 3)
    kk = jnp.full((n ** 3,), k, jnp.int32)
    got = _pick_axis(jnp.asarray(v), kk)
    want = _gather_dim(jnp.asarray(v), kk)
    np.testing.assert_array_equal(_bits(got), _bits(want))
    np.testing.assert_array_equal(_bits(got), v[:, k].view(np.uint32))


def test_triangle_test_bitmatches_gather_formulation(monkeypatch):
    """OpTriangle with the select against the same stages with the old
    per-lane gather: t_num, t_denom and hit bit for bit on an (R, 4) batch
    whose rays lie on both sides of the ``dir[kz] < 0`` swap."""
    from repro.core import datapath

    rng = np.random.default_rng(16)
    r, width = 512, 4
    origin = rng.uniform(-1, 1, (r, 3)).astype(np.float32)
    direction = rng.normal(size=(r, 3)).astype(np.float32)
    rays = make_ray(jnp.asarray(origin), jnp.asarray(direction))
    dir_kz = np.take_along_axis(direction, np.asarray(rays.kz)[:, None], 1)
    assert (dir_kz < 0).any() and (dir_kz > 0).any()
    rays = _lanes(rays, width)
    # triangles around points on the rays, so a good share of lanes hit
    t = rng.uniform(0.5, 3, (r, width, 1)).astype(np.float32)
    centre = origin[:, None] + t * direction[:, None]
    a, b, c = (jnp.asarray(centre + rng.normal(scale=0.5, size=(r, width, 3))
                           .astype(np.float32)) for _ in range(3))
    tris = Triangle(a=a, b=b, c=c)

    # op by op, not jitted: XLA's CPU backend contracts a fused multiply
    # and add into one FMA, and which pairs it fuses differs between the
    # two programs, so a jitted pair can differ in the last bit for reasons
    # that are not the pick's
    got = ray_triangle_test(rays, tris)
    monkeypatch.setattr(datapath, "_pick_axis", _gather_dim)
    want = datapath.ray_triangle_test(rays, tris)
    np.testing.assert_array_equal(_bits(got.t_num), _bits(want.t_num))
    np.testing.assert_array_equal(_bits(got.t_denom), _bits(want.t_denom))
    np.testing.assert_array_equal(np.asarray(got.hit), np.asarray(want.hit))
    assert 0 < np.asarray(got.hit).sum() < r * width


def test_triangle_test_lowers_without_gather():
    """The sheared-axis pick stays elementwise: OpTriangle on an (R, 4)
    batch lowers to no gather, which on the TPU took most of a frame."""
    r, width = 256, 4
    rays = make_ray(jnp.ones((r, 3)), jnp.ones((r, 3)))
    rays = _lanes(rays, width)
    v = jnp.zeros((r, width, 3))
    text = jax.jit(ray_triangle_test).lower(rays, Triangle(v, v, v)).as_text()
    assert "select" in text
    assert "gather" not in text
