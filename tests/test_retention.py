"""ops/retention.py: the three forms of power retention agree, and the
state's read-and-update (ops/kvcache.py::retention_read_and_update) keeps
what its contract says, bit for bit where it says so. Small sizes, CPU,
float32: the forms differ by summation order alone (measured 6e-6 on
outputs of magnitude 1; the limit leaves a factor of five)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from substratus_tpu.ops import kvcache
from substratus_tpu.ops import retention as R

D, H, KH, B, T = 16, 4, 2, 2, 13
F = R.width(D)
TOL = 3e-5


@pytest.fixture(scope="module")
def seq():
    """q, k, v and log g of two rows of 13 tokens, the gate's bias in
    [3, 7] so that the carried state matters to every later token."""
    ks = jax.random.split(jax.random.key(0), 4)
    return (jax.random.normal(ks[0], (B, T, H, D)),
            jax.random.normal(ks[1], (B, T, KH, D)),
            jax.random.normal(ks[2], (B, T, KH, D)),
            jax.nn.log_sigmoid(jax.random.uniform(ks[3], (B, T, KH),
                                                  minval=3.0, maxval=7.0)))


def zero_state(b=B):
    return jnp.zeros((b, KH, F, D)), jnp.zeros((b, KH, F))


def attention_form(q, k, v, log_g):
    """The equations as written, in numpy, one head at a time."""
    q, k, v, log_g = (np.asarray(a, np.float64) for a in (q, k, v, log_g))
    out = np.zeros((B, T, H, D))
    total = np.cumsum(log_g, axis=1)
    for b in range(B):
        for h in range(H):
            kh = h // (H // KH)
            for t in range(T):
                a = ((q[b, t, h] @ k[b, :t + 1, kh].T) ** 2 / D
                     * np.exp(total[b, t, kh] - total[b, :t + 1, kh]))
                out[b, t, h] = a @ v[b, :t + 1, kh] / (a.sum() + 1e-6)
    return out


def test_phi_is_the_second_powers_feature_map():
    """`phi(q) . phi(k)` equals `(q . k)^2`, at the width d (d + 1) / 2:
    136 for heads of 16, 8,256 for heads of 128."""
    a, b = jax.random.normal(jax.random.key(1), (2, 7, D))
    assert R.phi(a).shape == (7, F) and (F, R.width(128)) == (136, 8256)
    want = np.sum(np.asarray(a) * np.asarray(b), -1) ** 2
    assert np.abs(np.sum(R.phi(a) * R.phi(b), -1) - want).max() < 1e-4
    with pytest.raises(ValueError, match="even"):
        R.phi(jnp.zeros((3, 5)))


def test_the_recurrent_form_is_the_attention_form(seq):
    q, k, v, log_g = seq
    want = attention_form(*seq)
    s, z = zero_state()
    for t in range(T):
        s, z, o = R.step(s, z, q[:, t], k[:, t], v[:, t], log_g[:, t],
                         jnp.full((B,), t == 0))
        assert np.abs(np.asarray(o) - want[:, t]).max() < TOL, t
    assert np.abs(np.asarray(R.chunk(None, *seq)) - want).max() < TOL


@pytest.mark.parametrize("size", [1, 3, T, 5], ids=["1", "3", "C", "uneven"])
def test_the_chunked_form_is_the_attention_form(seq, size):
    """Chunks of 1, 3, the whole and 5 + 5 + 3, each from the state the
    chunk before left; the first from a state full of another occupant's
    numbers, which `fresh` hides. The state left is the recurrent one."""
    q, k, v, log_g = seq
    want = attention_form(*seq)
    s, z = jnp.full((B, KH, F, D), 7.0), jnp.full((B, KH, F), 3.0)
    outs = []
    for t in range(0, T, size):
        cut = slice(t, t + size)
        s, z, o = R.chunk((s, z), q[:, cut], k[:, cut], v[:, cut],
                          log_g[:, cut], jnp.full((B,), t == 0))
        outs.append(np.asarray(o))
    assert np.abs(np.concatenate(outs, 1) - want).max() < TOL
    rs, rz = zero_state()
    for t in range(T):
        rs, rz, _ = R.step(rs, rz, q[:, t], k[:, t], v[:, t], log_g[:, t],
                           jnp.zeros((B,), bool))
    assert np.abs(np.asarray(s - rs)).max() < TOL
    assert np.abs(np.asarray(z - rz)).max() < TOL


# -- the state in the cache dict ---------------------------------------------------

def _call(state, slots, positions, valid, q, k, v, log_g, layer=1):
    s, z, o = kvcache.retention_read_and_update(
        state[kvcache.RET_S], state[kvcache.RET_Z], jnp.int32(layer),
        None if slots is None else jnp.asarray(slots, jnp.int32),
        jnp.asarray(positions, jnp.int32), jnp.asarray(valid), q, k, v, log_g)
    return {kvcache.RET_S: s, kvcache.RET_Z: z}, np.asarray(o)


def stale(slots=3, layers=2):
    """A state in which every slot holds another occupant's numbers."""
    st = kvcache.init_retention_state(layers, slots, KH, D, D)
    assert st[kvcache.RET_S].shape == (layers, slots, KH, F, D)
    assert st[kvcache.RET_Z].shape == (layers, slots, KH, F)
    assert all(a.dtype == jnp.float32 for a in st.values())
    return {n: a + 1.0 + jnp.arange(slots).reshape((1, slots) + (1,) *
                                                   (a.ndim - 2))
            for n, a in st.items()}


def test_position_zero_starts_from_zero_and_an_offset_continues(seq):
    """A row whose first token is at position 0 starts from zero whatever
    its slot holds, and nothing was zeroed; the chunk after it continues
    from what it left; other slots and layers are not touched."""
    q, k, v, log_g = seq
    want = attention_form(*seq)
    st0 = stale()
    ones = np.ones((1, 8), bool)
    st, o = _call(st0, [2], np.arange(8)[None], ones, q[:1, :8], k[:1, :8],
                  v[:1, :8], log_g[:1, :8])
    assert np.abs(o - want[:1, :8]).max() < TOL
    st, o = _call(st, [2], 8 + np.arange(5)[None], ones[:, :5], q[:1, 8:],
                  k[:1, 8:], v[:1, 8:], log_g[:1, 8:])
    assert np.abs(o - want[:1, 8:]).max() < TOL
    for name in st:
        got, was = np.asarray(st[name]), np.asarray(st0[name])
        assert np.array_equal(got[0], was[0])  # layer 0
        assert np.array_equal(got[1, :2], was[1, :2])  # slots 0 and 1
        assert not np.array_equal(got[1, 2], was[1, 2])
    # from a wiped state the second chunk reads something else: the carry
    # is what it depends on
    wiped = {n: jnp.zeros_like(a) for n, a in st0.items()}
    _, lost = _call(wiped, [2], 8 + np.arange(5)[None], ones[:, :5],
                    q[:1, 8:], k[:1, 8:], v[:1, 8:], log_g[:1, 8:])
    assert np.abs(lost - want[:1, 8:]).max() > 1e-2


def test_a_padded_tail_does_not_enter_the_state(seq):
    """A bucket of 8 with 5 real tokens (the tail clamped one past them,
    as serve/engine.py pads a chunk): whatever the tail carries, the state
    left is the same bit for bit, and it is the state the 5 tokens alone
    leave (another shape of the same sums: to rounding)."""
    q, k, v, log_g = seq
    pos = np.minimum(np.arange(8), 5)[None]
    valid = np.arange(8)[None] < 5
    padded, o8 = _call(stale(), [1], pos, valid, q[:1, :8], k[:1, :8],
                       v[:1, :8], log_g[:1, :8])
    other, _ = _call(stale(), [1], pos, valid,
                     *[a[:1, :8].at[:, 5:].multiply(-3.0) for a in seq])
    alone, o5 = _call(stale(), [1], np.arange(5)[None], np.ones((1, 5), bool),
                      q[:1, :5], k[:1, :5], v[:1, :5], log_g[:1, :5])
    for name in padded:
        assert np.array_equal(np.asarray(padded[name]),
                              np.asarray(other[name]))
        assert np.abs(np.asarray(padded[name] - alone[name])).max() < TOL
    assert np.abs(o8[:, :5] - o5).max() < TOL


def test_an_idle_row_leaves_its_slots_state_bit_for_bit(seq):
    """A decode step over every slot (`slots` None: row i is slot i) in
    which row 1 is real: rows 0 and 2 keep their state bit for bit, though
    they sit at position 0 with filler in q, k, v and the gate."""
    q, k, v, log_g = seq
    st0 = stale()
    three = [jnp.concatenate([a[:, :1], a[:1, :1]]) for a in seq]
    st, _ = _call(st0, None, [[0], [4], [0]], [[False], [True], [False]],
                  *three)
    for name in st:
        got, was = np.asarray(st[name]), np.asarray(st0[name])
        assert np.array_equal(got[1, 0], was[1, 0])
        assert np.array_equal(got[1, 2], was[1, 2])
        assert not np.array_equal(got[1, 1], was[1, 1])
        assert np.array_equal(got[0], was[0])
    with pytest.raises(ValueError, match="slots"):
        _call(st0, None, [[4]], [[True]], *[a[:1, :1] for a in seq])


def test_rows_by_slot_and_the_slab_are_the_same_step(seq):
    """One token a row: addressed row by row (`slots`) or as the layer's
    slab (None), the states written and the outputs are the same bits."""
    three = [jnp.concatenate([a[:, :1], a[:1, 1:2]]) for a in seq]
    pos, valid = [[3], [0], [9]], np.ones((3, 1), bool)
    by_row, o1 = _call(stale(), [0, 1, 2], pos, valid, *three)
    slab, o2 = _call(stale(), None, pos, valid, *three)
    assert np.array_equal(o1, o2)
    for name in slab:
        assert np.array_equal(np.asarray(by_row[name]),
                              np.asarray(slab[name]))


# -- the decode step as one kernel (ops/retention_kernel.py) -----------------------

@pytest.mark.parametrize("group", [1, 5], ids=["G1", "G5"])
@pytest.mark.parametrize("row", ["live", "fresh", "idle"])
@pytest.mark.parametrize("d", [16, 128], ids=["d16-F136", "d128-F8256"])
def test_the_state_kernel_is_the_recurrent_step(d, row, group):
    """The kernel, interpreted, against `step` on layer 1 of a stack of two,
    two slots, float32 in and out: row 0 is a live row; row 1 is live, or
    fresh (position 0 over a slot that holds infinities: nothing of them
    is kept), or idle (`k = 0`, `log g = 0`: its `S` and `z` come back bit
    for bit). The two differ by the order of float32 sums alone. Layer 0
    is not touched."""
    from substratus_tpu.ops import retention_kernel

    kh, f = 2 if d == 16 else 1, R.width(d)
    ks = jax.random.split(jax.random.key(d + group), 6)
    # a state as three tokens leave it, so the normaliser is a sum of
    # squares and the division is as well conditioned as a served one
    seen = R.phi(jax.random.normal(ks[0], (2, 2, kh, 3, d)))
    s0 = jnp.einsum("lbkjf,lbkjd->lbkfd", seen,
                    jax.random.normal(ks[1], (2, 2, kh, 3, d)))
    z0 = seen.sum(axis=3)
    q = jax.random.normal(ks[2], (2, kh * group, d))
    k = jax.random.normal(ks[3], (2, kh, d))
    v = jax.random.normal(ks[4], (2, kh, d))
    log_g = jax.nn.log_sigmoid(
        jax.random.uniform(ks[5], (2, kh), minval=3.0, maxval=7.0))
    fresh = jnp.array([False, row == "fresh"])
    if row == "fresh":
        s0, z0 = s0.at[1, 1].set(jnp.inf), z0.at[1, 1].set(jnp.inf)
    if row == "idle":
        k, log_g = k.at[1].set(0), log_g.at[1].set(0)
    want_s, want_z, want_o = R.step(s0[1], z0[1], q, k, v, log_g, fresh)
    s1, z1, o = retention_kernel.step(
        s0, z0, jnp.int32(1), q, k, v, log_g, fresh, interpret=True)
    assert s1.dtype == z1.dtype == o.dtype == jnp.float32
    assert s1.shape == s0.shape and z1.shape == z0.shape

    def close(got, want):
        got, want = np.asarray(got), np.asarray(want)
        assert np.array_equal(np.isnan(got), np.isnan(want))
        scale = max(1.0, np.nanmax(np.abs(want)))
        return np.nanmax(np.abs(got - want)) / scale < TOL

    assert close(s1[1], want_s) and close(z1[1], want_z) and close(o, want_o)
    assert np.isfinite(np.asarray(s1)).all() and np.isfinite(z1).all()
    assert np.isfinite(np.asarray(o[0])).all()
    for got, was in ((s1, s0), (z1, z0)):
        assert np.array_equal(np.asarray(got[0]), np.asarray(was[0]))
        if row == "idle":
            assert np.array_equal(np.asarray(got[1, 1]).view(np.uint32),
                                  np.asarray(was[1, 1]).view(np.uint32))
            assert not np.array_equal(np.asarray(got[1, 0]),
                                      np.asarray(was[1, 0]))


def test_the_decode_step_takes_the_kernel_where_it_is_written_for_the_state(
        monkeypatch, pallas_interpret):
    """`retention_read_and_update` reads the choice off its inputs: the
    slab of a float32 state of 128-wide values, one token a row, lowered
    for a TPU, is the kernel's; a chunk, rows addressed by slot, a narrower
    value, another type and any other platform are ops/retention.py's."""
    f = R.width(128)
    st = {kvcache.RET_S: jnp.ones((1, 2, 1, f, 128)),
          kvcache.RET_Z: jnp.ones((1, 2, 1, f))}
    taken = kvcache._retention_kernel_for
    assert taken(st[kvcache.RET_S]) is not None
    assert taken(st[kvcache.RET_S].astype(jnp.bfloat16)) is None
    assert taken(jnp.ones((1, 2, 1, 136, 16))) is None
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    assert taken(shape(10, 16, 8, f, 128)) is not None  # the longctx cell's
    assert taken(shape(10, 16, 16, f, 128)) is None  # a slot's heads too many
    assert taken(shape(10, 16, 8, R.width(64), 128)) is None  # keys of 64
    # on the CPU that holds it the step is XLA's, whatever the shape
    assert not kvcache.retention_step_takes_kernel(st[kvcache.RET_S])
    ks = jax.random.split(jax.random.key(3), 4)
    args = (jax.random.normal(ks[0], (2, 1, 5, 128)),
            jax.random.normal(ks[1], (2, 1, 1, 128)),
            jax.random.normal(ks[2], (2, 1, 1, 128)),
            jax.nn.log_sigmoid(jnp.full((2, 1, 1), 5.0)))
    calls = []
    monkeypatch.setattr(
        kvcache.retention_kernel, "step",
        lambda *a, _k=kvcache.retention_kernel.step, **kw:
        calls.append(1) or _k(*a, **kw))
    want, o_want = _call(st, None, [[3], [0]], [[True], [True]], *args,
                         layer=0)
    assert not calls  # the platform is not a TPU
    monkeypatch.setattr(jax.lax, "platform_dependent",
                        lambda *a, tpu, default: tpu(*a))
    calls.clear()
    got, o_got = _call(st, None, [[3], [0]], [[True], [True]], *args, layer=0)
    assert calls
    assert np.abs(o_got - o_want).max() < TOL
    for name in st:
        assert np.abs(np.asarray(got[name] - want[name])).max() < TOL
    calls.clear()
    _call(st, [0, 1], [[3], [0]], [[True], [True]], *args, layer=0)
    two = [jnp.concatenate([a, a], axis=1) for a in args]
    _call(st, None, [[3, 4], [0, 1]], np.ones((2, 2), bool), *two, layer=0)
    assert not calls
