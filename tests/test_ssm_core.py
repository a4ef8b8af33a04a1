"""The recurrence of a Mamba-2 layer, its two forms and its state in slots
(models/nemotron_h.py): the chunked form against the recurrence token by
token, what padding leaves behind, the decode kernel in interpret mode
against its XLA twin, and what slots do and do not touch. float32 on the
CPU: the two forms differ by the rounding of sums, 1e-5 on values of order
1 - 10."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import nemotron_h as nh

TOL = 2e-5


def inputs(t, heads=4, p=8, groups=2, n=8, bp=2, seed=0):
    rs = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(rs.randn(*s), jnp.float32)     # noqa: E731
    dt = jnp.asarray(np.abs(rs.randn(bp, t, heads)) * 0.5, jnp.float32)
    a = -jnp.asarray(np.exp(rs.randn(heads) * 2.0), jnp.float32)
    return f(bp, t, heads, p), dt, a, f(bp, t, groups, n), f(bp, t, groups, n)


def token_by_token(x, dt, a, b, c, s0):
    """The definition: S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t,
    y_t = S_t C_t, one token at a time through the decode step's twin."""
    bp, t, heads, _ = x.shape
    state = jnp.concatenate([jnp.zeros_like(s0[:1]), s0])     # slot 0 scratch
    slots = jnp.arange(1, bp + 1, dtype=jnp.int32)
    ys = []
    for i in range(t):
        y, state = nh._ssm_decode_update_xla(
            state, slots, x[:, i], dt[:, i], a, b[:, i], c[:, i],
            jnp.zeros(heads))
        ys.append(y)
    return jnp.stack(ys, axis=1), state[1:]


@pytest.mark.parametrize("t,chunk", [(16, 8), (16, 16), (24, 8), (12, 128),
                                     (3, 8)])
@pytest.mark.parametrize("initial", [False, True], ids=["zero", "carried"])
def test_chunked_form_equals_the_recurrence(t, chunk, initial):
    x, dt, a, b, c = inputs(t)
    s0 = jnp.zeros((2, 4, 8, 8))
    if initial:
        s0 = jnp.asarray(np.random.RandomState(5).randn(2, 4, 8, 8),
                         jnp.float32)
    y, state = nh.ssm_chunk_scan(x, dt, a, b, c, s0, chunk)
    want_y, want_state = token_by_token(x, dt, a, b, c, s0)
    np.testing.assert_allclose(y, want_y, atol=TOL, rtol=TOL)
    np.testing.assert_allclose(state, want_state, atol=TOL, rtol=TOL)


def test_a_masked_position_leaves_the_state_where_it_was():
    """dt = 0 past the last real token: the state after a padded chunk is
    the state after its last real token."""
    x, dt, a, b, c = inputs(16)
    real = 11
    masked = dt.at[:, real:].set(0.0)
    _, padded = nh.ssm_chunk_scan(x, masked, a, b, c,
                                  jnp.zeros((2, 4, 8, 8)), 8)
    _, short = token_by_token(x[:, :real], dt[:, :real], a, b[:, :real],
                              c[:, :real], jnp.zeros((2, 4, 8, 8)))
    np.testing.assert_allclose(padded, short, atol=TOL, rtol=TOL)


@pytest.fixture(scope="module")
def layer():
    cfg = nh.NemotronHConfig.tiny(dtype=jnp.float32, pattern="M",
                                  num_layers=1)
    params = nh.init_params(jax.random.PRNGKey(3), cfg)
    return cfg, nh._layer_params(params, "mamba", 0)


def prefill(cfg, lp, hn, tail, ssm, slots, cached, seq):
    return nh.mamba_prefill(hn, lp, tail, ssm, jnp.asarray(slots, jnp.int32),
                            jnp.asarray(cached, jnp.int32),
                            jnp.asarray(seq, jnp.int32), cfg)


def fresh_state(cfg, slots=4):
    kc, vc = nh.init_cache(cfg, 2, slots)
    return kc[0], vc[0]


@pytest.mark.parametrize("real", [1, 2, 5, 8])
def test_padding_leaves_state_and_tail_where_the_last_real_token_left_them(
        layer, real):
    cfg, lp = layer
    hn = jnp.asarray(np.random.RandomState(1).randn(1, 8, cfg.hidden_size),
                     jnp.float32)
    tail, ssm = fresh_state(cfg)
    out_p, tail_p, ssm_p = prefill(cfg, lp, hn, tail, ssm, [2], [0], [real])
    tail, ssm = fresh_state(cfg)
    short = jnp.pad(hn[:, :real], ((0, 0), (0, 8 - real), (0, 0)))
    out_s, tail_s, ssm_s = prefill(cfg, lp, short, tail, ssm, [2], [0],
                                   [real])
    np.testing.assert_allclose(ssm_p[2], ssm_s[2], atol=TOL)
    np.testing.assert_allclose(tail_p[2], tail_s[2], atol=TOL)
    np.testing.assert_allclose(out_p[:, :real], out_s[:, :real], atol=TOL)
    # the tail is the last three REAL inputs: fewer than three keeps zeros
    # of the start in front
    assert bool(jnp.all(tail_p[2, :max(3 - real, 0)] == 0))


def test_chunks_of_one_and_two_tokens_carry_state_and_tail(layer):
    """A chunk shorter than the convolution keeps part of the old tail:
    16 tokens as 8 + 1 + 2 + 5 equal 16 at once."""
    cfg, lp = layer
    hn = jnp.asarray(np.random.RandomState(2).randn(1, 16, cfg.hidden_size),
                     jnp.float32)
    tail, ssm = fresh_state(cfg)
    whole, tail_w, ssm_w = prefill(cfg, lp, hn, tail, ssm, [1], [0], [16])
    tail, ssm = fresh_state(cfg)
    outs, at = [], 0
    for n in (8, 1, 2, 5):
        piece = jnp.pad(hn[:, at:at + n], ((0, 0), (0, 8 - n), (0, 0)))
        out, tail, ssm = prefill(cfg, lp, piece, tail, ssm, [1], [at],
                                 [at + n])
        outs.append(out[:, :n])
        at += n
    np.testing.assert_allclose(jnp.concatenate(outs, axis=1), whole,
                               atol=TOL, rtol=TOL)
    np.testing.assert_allclose(ssm[1], ssm_w[1], atol=TOL, rtol=TOL)
    np.testing.assert_allclose(tail[1], tail_w[1], atol=TOL)


def test_a_first_chunk_starts_from_zero_whatever_the_slot_holds(layer):
    cfg, lp = layer
    hn = jnp.asarray(np.random.RandomState(4).randn(1, 8, cfg.hidden_size),
                     jnp.float32)
    tail, ssm = fresh_state(cfg)
    out, tail_c, ssm_c = prefill(cfg, lp, hn, tail, ssm, [3], [0], [8])
    out_d, tail_d, ssm_d = prefill(cfg, lp, hn, tail + 7.0, ssm - 3.0, [3],
                                   [0], [8])
    np.testing.assert_array_equal(out_d, out)
    np.testing.assert_array_equal(tail_d[3], tail_c[3])
    np.testing.assert_array_equal(ssm_d[3], ssm_c[3])


def test_padding_rows_write_slot_zero_and_no_live_slot_changes(layer):
    cfg, lp = layer
    rs = np.random.RandomState(6)
    hn = jnp.asarray(rs.randn(4, 8, cfg.hidden_size), jnp.float32)
    tail, ssm = fresh_state(cfg, slots=5)
    tail = tail.at[1:].set(jnp.asarray(rs.randn(4, 3, cfg.conv_dim),
                                       jnp.float32))
    ssm = ssm.at[1:].set(jnp.asarray(rs.randn(4, 4, 16, 16), jnp.float32))
    # rows 1 and 3 are padding (slot 0, seq_len == cached_len == 0); slot
    # 4 belongs to a sequence not in this round
    _, tail2, ssm2 = prefill(cfg, lp, hn, tail, ssm, [2, 0, 1, 0],
                             [8, 0, 0, 0], [16, 0, 8, 0])
    for before, after in ((tail, tail2), (ssm, ssm2)):
        np.testing.assert_array_equal(after[0], before[0])    # stays zero
        np.testing.assert_array_equal(after[3], before[3])
        np.testing.assert_array_equal(after[4], before[4])
        assert not np.allclose(after[1], before[1])
        assert not np.allclose(after[2], before[2])


def step_inputs(lanes=6, heads=4, p=16, groups=2, n=16, slots=7, seed=0):
    rs = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(rs.randn(*s), jnp.float32)     # noqa: E731
    return dict(state=f(slots, heads, p, n).at[0].set(0.0),
                x=f(lanes, heads, p), dt=jnp.abs(f(lanes, heads)),
                a=-jnp.abs(f(heads)), b=f(lanes, groups, n),
                c=f(lanes, groups, n), d=f(heads))


def run_step(fn, i, slots, **kw):
    return fn(i["state"], jnp.asarray(slots, jnp.int32), i["x"], i["dt"],
              i["a"], i["b"], i["c"], i["d"], **kw)


@pytest.mark.parametrize("heads,p,groups,n", [
    (4, 16, 2, 16), (8, 8, 8, 128), (6, 8, 1, 32),
    (64, 64, 8, 128),       # the Nemotron cell's own: 2 MiB a lane
], ids=["small", "a-head-a-group", "one-group", "the-cell"])
def test_the_kernel_in_interpret_mode_equals_its_xla_twin(heads, p, groups,
                                                          n):
    """TOL as for the chunked form. The state is elementwise, the same
    operations in the same order on both sides. y is a sum of N float32
    products a row on both sides, the kernel's by a lane reduction over
    the tile it has just written, the twin's by an einsum at full
    precision: they differ by the order of the additions only, N x 2**-24
    of the terms' size at worst (1e-5 at N = 128), whatever order a body
    sums in, as long as it sums float32 products in float32."""
    i = step_inputs(heads=heads, p=p, groups=groups, n=n)
    slots = [3, 1, 6, 4, 2, 5]
    y, state = run_step(nh.ssm_decode_update, i, slots, interpret=True)
    want_y, want_state = run_step(nh._ssm_decode_update_xla, i, slots)
    np.testing.assert_allclose(y, want_y, atol=TOL, rtol=TOL)
    np.testing.assert_allclose(state, want_state, atol=TOL, rtol=TOL)


@pytest.mark.parametrize("a_batch", [1, 2, 3, 6])
def test_the_queue_in_batches_of_any_size_keeps_slot_zero_and_the_live_slots(
        a_batch, monkeypatch, capfd):
    """Six lanes as 6, 3, 2 batches and as 1: lanes 1 and 4 are invalid
    (slot 0, dt = 0) BETWEEN live lanes, in one batch of the queue or in
    two. Slot 0 keeps its bits and the slots of no lane keep theirs; a
    live slot holds what the twin gives (the update is elementwise: to the
    bit on the chip, within a fused multiply-add's rounding where XLA's
    CPU backend fuses one side) and, to the bit, what the queue gives as
    ONE batch: every buffer goes back to the slot it was read from. In the
    TPU interpreter, which keeps the copies' and the body's clocks: no
    buffer is read or written while a copy of it is in flight."""
    from jax.experimental.pallas import tpu as pltpu

    i = step_inputs()
    i["dt"] = i["dt"].at[jnp.asarray([1, 4])].set(0.0)
    slots = [3, 0, 6, 4, 0, 5]
    tpu = pltpu.InterpretParams(detect_races=True)
    one_y, one_state = run_step(nh.ssm_decode_update, i, slots,
                                interpret=tpu)
    monkeypatch.setattr(nh, "_SSM_PHASE_BYTES",
                        a_batch * i["state"][0].size * 4)
    y, state = run_step(nh.ssm_decode_update, i, slots, interpret=tpu)
    assert "RACE DETECTED" not in capfd.readouterr().out
    want_y, want_state = run_step(nh._ssm_decode_update_xla, i, slots)
    np.testing.assert_array_equal(state[0], 0.0)
    for untouched in (1, 2):
        np.testing.assert_array_equal(state[untouched], i["state"][untouched])
    np.testing.assert_array_equal(state, one_state)
    for live in (3, 4, 5, 6):
        np.testing.assert_allclose(state[live], want_state[live], atol=TOL,
                                   rtol=TOL)
        assert not np.allclose(state[live], i["state"][live])
    live_lanes = np.asarray([0, 2, 3, 5])
    np.testing.assert_array_equal(y[live_lanes], one_y[live_lanes])
    np.testing.assert_allclose(y[live_lanes], want_y[live_lanes], atol=TOL,
                               rtol=TOL)


@pytest.fixture
def pallas_impl(monkeypatch):
    # use_pallas() asks the default backend, the CPU here: answer as on
    # the chip
    from dynamo_tpu.engine import attention

    monkeypatch.setattr(attention, "_impl", "pallas")


@pytest.mark.parametrize("heads,p,n,runs", [
    (64, 64, 128, True),        # the cell's
    (8, 8, 128, True),
    (6, 8, 32, False),          # a state narrower than a tile's lanes
    (64, 12, 128, False),       # a head's rows not whole tiles
    (130, 64, 128, False),      # more heads than one tile's lanes
    (128, 128, 256, False),     # a lane's state (16 MiB) over a phase
])
def test_the_gate_asks_whole_tiles_and_a_lane_a_phase_can_hold(
        heads, p, n, runs, pallas_impl):
    assert nh.ssm_kernel_runs(heads, p, n) is runs


def test_a_shape_the_gate_refuses_takes_the_xla_twin(pallas_impl,
                                                     monkeypatch):
    monkeypatch.setattr(nh, "_ssm_decode_update_kernel", None)
    i = step_inputs(heads=6, p=8, groups=1, n=32)
    slots = [3, 1, 6, 4, 2, 5]
    y, state = run_step(nh.ssm_decode_update, i, slots)
    want_y, want_state = run_step(nh._ssm_decode_update_xla, i, slots)
    np.testing.assert_array_equal(y, want_y)
    np.testing.assert_array_equal(state, want_state)


@pytest.mark.parametrize("interpret", [None, True], ids=["xla", "kernel"])
def test_a_permutation_of_slots_permutes_nothing_else(interpret):
    i = step_inputs()
    slots, perm = np.asarray([3, 1, 6, 4, 2, 5]), np.asarray([4, 2, 0, 5, 1, 3])
    y, state = run_step(nh.ssm_decode_update, i, slots, interpret=interpret)
    moved = dict(i, x=i["x"][perm], dt=i["dt"][perm], b=i["b"][perm],
                 c=i["c"][perm])
    y2, state2 = run_step(nh.ssm_decode_update, moved, slots[perm],
                          interpret=interpret)
    np.testing.assert_allclose(y2, y[perm], atol=1e-6)
    np.testing.assert_allclose(state2, state, atol=1e-6)


@pytest.mark.parametrize("interpret", [None, True], ids=["xla", "kernel"])
def test_invalid_lanes_stand_at_slot_zero_and_leave_it_zero(interpret):
    i = step_inputs()
    i["dt"] = i["dt"].at[jnp.asarray([1, 4])].set(0.0)  # what `valid` does
    _, state = run_step(nh.ssm_decode_update, i, [3, 0, 6, 4, 0, 5],
                        interpret=interpret)
    np.testing.assert_array_equal(state[0], 0.0)
    for untouched in (1, 2):
        np.testing.assert_array_equal(state[untouched], i["state"][untouched])
    for live in (3, 4, 5, 6):
        assert not np.allclose(state[live], i["state"][live])
