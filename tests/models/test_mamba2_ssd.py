"""Mamba-2 SSD correctness: the chunked algorithm must equal the naive
step-by-step state-space recurrence, and decode must equal prefill."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.models import get_config, init_params
from repro.models.mamba2 import (init_mamba2, mamba2_decode_step,
                                 mamba2_forward, mamba2_init_cache,
                                 ssd_chunked)


def ssd_reference(x, dt, A, Bm, Cm):
    """Naive O(S) recurrence: h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T."""
    b, s, h, p = x.shape
    n = Bm.shape[-1]
    st = np.zeros((b, h, p, n), np.float64)
    ys = []
    x64 = np.asarray(x, np.float64)
    dt64 = np.asarray(dt, np.float64)
    A64 = np.asarray(A, np.float64)
    B64 = np.asarray(Bm, np.float64)
    C64 = np.asarray(Cm, np.float64)
    for t in range(s):
        dec = np.exp(dt64[:, t] * A64[None, :])            # (b, h)
        xdt = x64[:, t] * dt64[:, t][..., None]            # (b, h, p)
        st = st * dec[..., None, None] + \
            np.einsum("bhp,bn->bhpn", xdt, B64[:, t])
        ys.append(np.einsum("bhpn,bn->bhp", st, C64[:, t]))
    return np.stack(ys, axis=1), st


@pytest.mark.parametrize("s,chunk", [(16, 4), (32, 8), (24, 8), (64, 16),
                                     (512, 256)])
@pytest.mark.parametrize("seed", [0, 1])
def test_ssd_chunked_matches_recurrence(s, chunk, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    b, h, p, n = 2, 3, 4, 8
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
    Bm = jax.random.normal(ks[3], (b, s, n)) * 0.5
    Cm = jax.random.normal(jax.random.PRNGKey(seed + 10), (b, s, n)) * 0.5
    y, st = ssd_chunked(x, dt, A, Bm, Cm, chunk)
    y_ref, st_ref = ssd_reference(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), y_ref, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(st), st_ref, rtol=2e-4, atol=2e-4)


def ssd_chunked_cumsum(x, dt, A, Bm, Cm, chunk):
    """The chunked SSD with its in-chunk prefix sums as ``jnp.cumsum``: the
    formula the matmul form replaced, kept as an oracle."""
    b, s, h, p = x.shape
    n = Bm.shape[-1]
    c = s // chunk
    xd = x * dt[..., None]
    dAc = (dt * A[None, None, :]).reshape(b, c, chunk, h).transpose(0, 3, 1, 2)
    xc = xd.reshape(b, c, chunk, h, p)
    Bc = Bm.reshape(b, c, chunk, n)
    Cc = Cm.reshape(b, c, chunk, n)
    A_cum = jnp.cumsum(dAc, axis=-1)
    diff = A_cum[..., :, None] - A_cum[..., None, :]
    L = jnp.exp(jnp.where(jnp.tril(jnp.ones((chunk, chunk), bool)), diff,
                          -jnp.inf))
    Y_diag = jnp.einsum("bcln,bcsn,bhcls,bcshp->bclhp", Cc, Bc, L, xc)
    decay_states = jnp.exp(A_cum[..., -1:] - A_cum)
    states = jnp.einsum("bcln,bhcl,bclhp->bchpn", Bc, decay_states, xc)

    def step(st_in, inp):
        dec, st_chunk = inp
        return st_in * dec[..., None, None] + st_chunk, st_in

    final_state, states_in = lax.scan(
        step, jnp.zeros((b, h, p, n), x.dtype),
        (jnp.exp(A_cum[..., -1]).transpose(2, 0, 1),
         states.transpose(1, 0, 2, 3, 4)))
    Y_off = jnp.einsum("bcln,bchpn,bhcl->bclhp", Cc,
                       states_in.transpose(1, 0, 2, 3, 4), jnp.exp(A_cum))
    return (Y_diag + Y_off).reshape(b, s, h, p), final_state


def _published_inputs(seed, b, s, h, p, n):
    """dt log-uniform in [1e-3, 1e-1] and A uniform in [-16, -1], as
    Mamba-2's published initialisation draws them."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jnp.exp(jax.random.uniform(ks[1], (b, s, h), minval=np.log(1e-3),
                                    maxval=np.log(1e-1)))
    A = -jax.random.uniform(ks[2], (h,), minval=1.0, maxval=16.0)
    Bm = jax.random.normal(ks[3], (b, s, n)) * 0.5
    Cm = jax.random.normal(ks[4], (b, s, n)) * 0.5
    return x, dt, A, Bm, Cm


@pytest.mark.parametrize("seed", [0, 1])
def test_ssd_matmul_prefix_sums_match_cumsum(seed):
    """Forward and every input's gradient equal the cumsum formula at the
    training chunk of 256."""
    chunk = 256
    args = _published_inputs(seed, 2, 512, 3, 4, 8)
    wy = jax.random.normal(jax.random.PRNGKey(seed + 20), (2, 512, 3, 4))
    ws = jax.random.normal(jax.random.PRNGKey(seed + 30), (2, 3, 4, 8))

    def loss(fn):
        def f(*a):
            y, st = fn(*a, chunk)
            return jnp.sum(y * wy) + jnp.sum(st * ws)
        return f

    y, st = ssd_chunked(*args, chunk)
    y_ref, st_ref = ssd_chunked_cumsum(*args, chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_ref),
                               rtol=1e-5, atol=1e-5)
    argnums = tuple(range(5))
    grads = jax.grad(loss(ssd_chunked), argnums)(*args)
    grads_ref = jax.grad(loss(ssd_chunked_cumsum), argnums)(*args)
    # atol scales with the leaf: dt's gradient reaches about 20 and sums
    # terms that cancel, so both forms sit up to 7e-5 from a float64 run.
    for name, g, g_ref in zip(("x", "dt", "A", "Bm", "Cm"), grads, grads_ref):
        g_ref = np.asarray(g_ref)
        np.testing.assert_allclose(np.asarray(g), g_ref, rtol=1e-5,
                                   atol=1e-5 * np.abs(g_ref).max(),
                                   err_msg=name)


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of its sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def test_ssd_prefix_sums_are_a_highest_precision_matmul():
    """At the mamba2-130m cell's shapes, neither the SSD nor its gradient
    holds a cumsum, and each prefix-sum matmul (an operand of shape
    (chunk, chunk)) runs at HIGHEST precision: the CPU ignores precision,
    so this is what keeps the TPU from rounding dt * A to bf16."""
    b, s, h, p, n, chunk = 8, 2048, 24, 64, 128, 256
    f32 = jnp.float32
    shapes = (jax.ShapeDtypeStruct((b, s, h, p), f32),
              jax.ShapeDtypeStruct((b, s, h), f32),
              jax.ShapeDtypeStruct((h,), f32),
              jax.ShapeDtypeStruct((b, s, n), f32),
              jax.ShapeDtypeStruct((b, s, n), f32))

    def fwd(*a):
        return ssd_chunked(*a, chunk)

    def grad(*a):
        return jax.grad(lambda *a: sum(jnp.sum(o) for o in fwd(*a)),
                        range(5))(*a)

    for fn in (fwd, grad):
        eqns = list(_eqns(jax.make_jaxpr(fn)(*shapes).jaxpr))
        assert not [e for e in eqns if "cumsum" in e.primitive.name]
        prefix = [e for e in eqns if e.primitive.name == "dot_general"
                  and any(v.aval.shape == (chunk, chunk) for v in e.invars)]
        assert prefix
        for e in prefix:
            assert e.params["precision"] == (lax.Precision.HIGHEST,
                                             lax.Precision.HIGHEST)


def test_ssd_initial_state_continuation():
    """Splitting a sequence in two with state carry == one full pass."""
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    b, s, h, p, n, chunk = 1, 32, 2, 4, 8, 8
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
    Bm = jax.random.normal(ks[3], (b, s, n)) * 0.5
    Cm = jax.random.normal(ks[4], (b, s, n)) * 0.5
    y_full, st_full = ssd_chunked(x, dt, A, Bm, Cm, chunk)
    half = s // 2
    y1, st1 = ssd_chunked(x[:, :half], dt[:, :half], A, Bm[:, :half],
                          Cm[:, :half], chunk)
    y2, st2 = ssd_chunked(x[:, half:], dt[:, half:], A, Bm[:, half:],
                          Cm[:, half:], chunk, init_state=st1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(st2), np.asarray(st_full),
                               rtol=2e-4, atol=2e-4)


def test_mamba_layer_decode_matches_forward():
    """Stepping the recurrent decode path over a sequence must match the
    chunked full-sequence forward of the same layer."""
    cfg = get_config("mamba2-130m", "smoke").with_(dtype="float32")
    key = jax.random.PRNGKey(0)
    p = init_mamba2(key, cfg, jnp.float32)
    B, S = 2, 16
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model)) * 0.3
    y_full = mamba2_forward(p, x, cfg)
    cache = mamba2_init_cache(cfg, B)
    outs = []
    for t in range(S):
        y1, cache = mamba2_decode_step(p, x[:, t:t + 1], cache, cfg)
        outs.append(y1)
    y_step = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(y_step), np.asarray(y_full),
                               rtol=5e-3, atol=5e-3)


def test_full_model_decode_matches_forward_mamba():
    """End-to-end parity for the pure-SSM architecture."""
    from repro.models import decode_step, forward, init_cache
    cfg = get_config("mamba2-130m", "smoke").with_(dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, S = 1, 12
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0,
                              cfg.vocab_size, dtype=jnp.int32)
    logits_ref, _ = forward(params, toks, cfg)
    cache = init_cache(cfg, B, max_len=S)
    last = None
    for t in range(S):
        last, cache = decode_step(params, cache, toks[:, t:t + 1], cfg)
    np.testing.assert_allclose(np.asarray(last[:, 0]),
                               np.asarray(logits_ref[:, -1]),
                               rtol=2e-3, atol=2e-3)


def test_full_model_decode_matches_forward_hybrid():
    """End-to-end parity for the hybrid (Jamba-style) architecture."""
    from repro.models import decode_step, forward, init_cache
    cfg = get_config("jamba-v0.1-52b", "smoke").with_(dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, S = 1, 8
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0,
                              cfg.vocab_size, dtype=jnp.int32)
    logits_ref, _ = forward(params, toks, cfg)
    cache = init_cache(cfg, B, max_len=S)
    last = None
    for t in range(S):
        last, cache = decode_step(params, cache, toks[:, t:t + 1], cfg)
    np.testing.assert_allclose(np.asarray(last[:, 0]),
                               np.asarray(logits_ref[:, -1]),
                               rtol=5e-3, atol=5e-3)
