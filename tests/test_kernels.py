"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret=True)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

rng = np.random.default_rng(0)


# ----------------------------------------------------------- LBS kernel
def _degrees(kind, w):
    """Row degrees of a wavefront of ``w`` popped tasks."""
    if kind == "uniform":
        return rng.integers(0, 9, size=w)
    deg = np.zeros(w, np.int64)
    if kind == "hub":            # hubs spanning many tiles, total > budget
        deg[:] = rng.integers(0, 30, size=w)
        deg[[100, 2000]] = [40_000, 100_000]
    elif kind == "invalid_tail":  # low degree, empty tail: total << budget
        deg[:1600] = rng.integers(5, 20, size=1600)
    elif kind == "spread_zeros":  # the window spans every scan chunk
        deg[::37] = rng.integers(1, 3, size=deg[::37].shape[0])
    elif kind == "wide":          # odd budget, last tiles partly live
        deg[:] = rng.integers(0, 60, size=w)
    return deg                   # "zero": an all-zero scan


# the benchmark's shapes: wavefront 4096, budget 2^17 or floored at a
# graph's largest degree (tests/test_tpu_compile.py)
LBS_CASES = [pytest.param(w, b, "uniform", id=f"{w}-{b}")
             for w, b in [(1, 128), (7, 64), (32, 1024), (100, 2048),
                          (257, 4096), (1000, 1024)]] + [
    pytest.param(4096, 131072, kind, id=kind)
    for kind in ("hub", "invalid_tail", "spread_zeros", "zero")] + [
    pytest.param(4096, 163_558, "wide", id="wide-163558")]


@pytest.mark.parametrize("w,budget,kind", LBS_CASES)
def test_lbs_kernel_matches_ref(w, budget, kind):
    from repro.kernels.frontier_expand.kernel import lbs_pallas
    from repro.kernels.frontier_expand.ref import lbs_ref

    scan = jnp.cumsum(jnp.asarray(_degrees(kind, w), jnp.int32))
    o1, r1 = lbs_pallas(scan, budget)
    o2, r2 = lbs_ref(scan, budget)
    total = min(int(scan[-1]), budget)
    np.testing.assert_array_equal(np.asarray(o1[:total]), np.asarray(o2[:total]))
    np.testing.assert_array_equal(np.asarray(r1[:total]), np.asarray(r2[:total]))


@pytest.mark.parametrize("kind", ["invalid_tail", "spread_zeros", "zero"])
def test_lbs_kernel_lanes_past_total(kind):
    """Lanes at or past the total read what the full compare-count gives
    there: every padded row counted (owner = W rounded up to 128) and
    rank = k - scan[-1]."""
    from repro.kernels.frontier_expand.kernel import lbs_pallas

    w, budget = 4000, 131072
    scan = jnp.cumsum(jnp.asarray(_degrees(kind, w), jnp.int32))
    owner, rank = lbs_pallas(scan, budget)
    total = int(scan[-1])
    k = np.arange(total, budget)
    np.testing.assert_array_equal(np.asarray(owner[total:]), 4096)
    np.testing.assert_array_equal(np.asarray(rank[total:]), k - total)


def test_lbs_kernel_zero_degrees():
    from repro.kernels.frontier_expand.kernel import lbs_pallas
    from repro.kernels.frontier_expand.ref import lbs_ref
    deg = np.array([0, 0, 5, 0, 3, 0], np.int32)
    scan = jnp.cumsum(jnp.asarray(deg))
    o1, r1 = lbs_pallas(scan, 16)
    o2, r2 = lbs_ref(scan, 16)
    np.testing.assert_array_equal(np.asarray(o1[:8]), np.asarray(o2[:8]))
    assert set(np.asarray(o1[:8]).tolist()) <= {2, 4}  # only nonzero rows own


def test_frontier_expand_op_equals_core():
    from repro.core.frontier import expand_merge_path
    from repro.kernels.frontier_expand.ops import frontier_expand
    from repro.graph import rmat

    g = rmat(7, 4, seed=5)
    items = jnp.array([1, 4, 9, 16, 25, 36, 49, 64], jnp.int32)
    valid = jnp.array([True] * 7 + [False])
    budget = 8 * int(jnp.max(g.degrees()))
    a = frontier_expand(items, valid, g.row_ptr, g.col_idx, budget)
    b = expand_merge_path(items, valid, g.row_ptr, g.col_idx, budget)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_frontier_expand_multi_tile_chunked_wraparound_parity():
    """The regime the smoke test above never reaches: a width-4 chunked
    wavefront popped across a wrapped ring head, whose degree-sum spills
    past one LBS tile (budget 4096 > TILE) — all three expansion backends
    (jnp reference, Pallas kernel, megakernel DMA stream) must agree on
    every output lane, exactly as the drain loops interleave them."""
    from repro.core import ChunkCodec, make_queue
    from repro.core.backend import STREAM
    from repro.core.frontier import chunk_degrees, expand_merge_path
    from repro.kernels.frontier_expand.ops import frontier_expand
    from repro.graph import rmat

    g = rmat(8, 8, seed=3)
    codec = ChunkCodec(4)
    n, W, cap, budget = g.num_vertices, 64, 64, 4096

    local = np.random.default_rng(7)
    def chunks(k, base):
        heads = local.integers(0, n - 4, size=k).astype(np.int32) + base
        widths = local.integers(1, 5, size=k).astype(np.int32)
        return codec.encode(jnp.asarray(heads % (n - 4)), jnp.asarray(widths))

    # rotate the ring so the popped wavefront physically wraps: after
    # push 48 / pop 40 / push 48 the live window is slots 40..95 (mod 64)
    q = make_queue(cap)
    q = q.push_dense(chunks(48, 0))
    _, _, q = q.pop(40)
    q = q.push_dense(chunks(48, 100))
    head_before = int(q.head)
    items, valid, q = q.pop(W)
    n_popped = int(np.asarray(valid).sum())
    assert n_popped == 56
    assert head_before + n_popped > cap  # the pop really crossed the seam

    safe = jnp.where(valid, items, 0)          # EMPTY lanes, as bfs.py does
    heads, widths = codec.decode(safe)
    assert int(jnp.cumsum(chunk_degrees(heads, widths, valid,
                                        g.row_ptr))[-1]) > 1024  # multi-tile
    ref = expand_merge_path(heads, valid, g.row_ptr, g.col_idx, budget,
                            widths=widths, max_width=4)
    pal = frontier_expand(heads, valid, g.row_ptr, g.col_idx, budget,
                          widths=widths, max_width=4)
    stream = expand_merge_path(heads, valid, g.row_ptr, g.col_idx, budget,
                               backend=STREAM, widths=widths, max_width=4)
    for got in (pal, stream):
        for x, y in zip(got, ref):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ------------------------------------------------------- compact kernel
@pytest.mark.parametrize("n", [1, 5, 255, 256, 257, 1000, 2048])
@pytest.mark.parametrize("p", [0.0, 0.3, 1.0])
def test_compact_matches_ref(n, p):
    from repro.kernels.queue_compact.ops import compact
    from repro.kernels.queue_compact.ref import compact_ref

    items = jnp.asarray(rng.integers(-1000, 1000, size=n), jnp.int32)
    mask = jnp.asarray(rng.random(n) < p)
    o1, c1 = compact(items, mask)
    o2, c2 = compact_ref(items, mask)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    assert int(c1) == int(c2)


def test_compact_is_stable():
    from repro.kernels.queue_compact.ops import compact
    items = jnp.arange(600, dtype=jnp.int32)
    mask = jnp.asarray(np.arange(600) % 3 == 0)
    out, cnt = compact(items, mask)
    got = np.asarray(out)[:int(cnt)]
    assert (np.diff(got) > 0).all()  # order preserved


# ------------------------------------------------------- flash attention
@pytest.mark.parametrize("bh,bkv,s,d", [(2, 2, 128, 128), (4, 2, 256, 128),
                                        (4, 1, 256, 256)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_ref_f32(bh, bkv, s, d, causal):
    from repro.kernels.flash_attention.kernel import flash_attention_pallas
    from repro.kernels.flash_attention.ref import attention_ref

    q = jnp.asarray(rng.standard_normal((bh, s, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((bkv, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((bkv, s, d)), jnp.float32)
    out = flash_attention_pallas(q, k, v, causal=causal)
    ref = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_bf16():
    from repro.kernels.flash_attention.kernel import flash_attention_pallas
    from repro.kernels.flash_attention.ref import attention_ref

    q = jnp.asarray(rng.standard_normal((2, 128, 128)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((2, 128, 128)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((2, 128, 128)), jnp.bfloat16)
    out = flash_attention_pallas(q, k, v)
    ref = attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(ref, dtype=np.float32),
                               atol=3e-2, rtol=3e-2)


def test_flash_sliding_window():
    from repro.kernels.flash_attention.kernel import flash_attention_pallas
    from repro.kernels.flash_attention.ref import attention_ref

    q = jnp.asarray(rng.standard_normal((2, 256, 128)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 256, 128)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 256, 128)), jnp.float32)
    out = flash_attention_pallas(q, k, v, causal=True, window=64)
    ref = attention_ref(q, k, v, causal=True, window=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_mha_wrapper_xla_vs_pallas():
    from repro.kernels.flash_attention.ops import multihead_attention

    b, s, h, kvh, d = 2, 128, 4, 2, 128
    q = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, kvh, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, kvh, d)), jnp.float32)
    a = multihead_attention(q, k, v, impl="xla")
    p = multihead_attention(q, k, v, impl="pallas")
    np.testing.assert_allclose(np.asarray(a), np.asarray(p), atol=2e-5,
                               rtol=2e-5)
