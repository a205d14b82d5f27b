"""Plain reference of the streaming recovery service's per-slot math.

Written from the MERINDA method and the service's documented semantics, in
straightforward ``jax.numpy`` at float32 with every matrix product at full
float32 precision (``mm``). It imports nothing of the program and takes
nothing the program made: initial weights come from the documented
initialisation recipe and the run's service seed, inputs from the benchmark's
own fleet.

Per slot and tick (``replay``): the ring buffer drops its oldest ``chunk``
samples and appends the new ones; windows of the buffer, z-scored with the
statistics frozen at admission, go through ``K`` AdamW steps on the
reconstruction loss (SINDy library of the windows integrated by RK4 from each
window's first sample) plus an L1 penalty on the coefficients; the readout is
the mean over windows of the head's coefficients, smoothed by an EMA that
the first tick after admission seeds; ``delta`` is the readout's relative
change.

``mm`` is the one matrix product. ``highest`` multiplies at full float32
precision. ``bf16x3`` is the control: the same product in three bfloat16
passes (hi*hi + hi*lo + lo*hi, float32 accumulation), the nearest precision
below, forward and backward, computed the same way on every platform.
"""

from __future__ import annotations

import functools
import importlib.util
import itertools
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

HERE = Path(__file__).resolve().parent
WARMUP = 50.0  # steps of linear learning-rate warm-up before the 1/sqrt decay
RMS_EPS = 1e-6
BETA1, BETA2, ADAM_EPS, WEIGHT_DECAY, CLIP = 0.9, 0.999, 1e-8, 1e-4, 1.0
STD_EPS = 1e-6


def _mm_highest(a, b):
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _split(x):
    """x = hi + lo + O(2^-16 |x|), hi and lo bfloat16. ``reduce_precision`` and
    not a round trip through bfloat16, which XLA may elide (excess precision)
    and so lose the low part."""
    hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    lo = jax.lax.reduce_precision(x - hi, exponent_bits=8, mantissa_bits=7)
    return hi.astype(jnp.bfloat16), lo.astype(jnp.bfloat16)


def _mm3(a, b):
    ah, al = _split(a)
    bh, bl = _split(b)

    def dot(x, y):
        return jnp.matmul(x, y, preferred_element_type=jnp.float32)

    return dot(ah, bh) + (dot(ah, bl) + dot(al, bh))


@jax.custom_vjp
def _mm_bf16x3(a, b):
    return _mm3(a, b)


def _mm_bf16x3_fwd(a, b):
    return _mm3(a, b), (a, b)


def _mm_bf16x3_bwd(res, g):
    a, b = res
    return _mm3(g, jnp.swapaxes(b, -1, -2)), _mm3(jnp.swapaxes(a, -1, -2), g)


_mm_bf16x3.defvjp(_mm_bf16x3_fwd, _mm_bf16x3_bwd)

PRECISIONS = {"highest": _mm_highest, "bf16x3": _mm_bf16x3}


@functools.lru_cache(maxsize=None)
def encoder_module(name: str):
    """The plain reference of encoder ``name``: ``reference/<name>.py``."""
    spec = importlib.util.spec_from_file_location(f"perfbench_ref_{name}", HERE / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def n_terms(n_vars: int, order: int) -> int:
    return len(monomials(n_vars, order))


def monomials(n_vars: int, order: int) -> list[tuple[int, ...]]:
    """Graded-lex monomials: the constant, then each degree's variable
    combinations with repetition in lexicographic order."""
    out = []
    for degree in range(order + 1):
        out.extend(itertools.combinations_with_replacement(range(n_vars), degree))
    return out


def library(z, order: int):
    """z [..., n_vars] -> monomial features [..., n_terms]."""
    cols = []
    for combo in monomials(z.shape[-1], order):
        col = jnp.ones(z.shape[:-1], z.dtype)
        for i in combo:
            col = col * z[..., i]
        cols.append(col)
    return jnp.stack(cols, axis=-1)


def init_params(key, cfg: dict) -> dict:
    """The service's initialisation recipe for one slot's model."""
    d_in = cfg["state_dim"] + cfg["input_dim"]
    H, Dh = cfg["hidden"], cfg["dense_hidden"]
    n_out = n_terms(d_in, cfg["order"]) * cfg["state_dim"]
    k_enc, k1, k2 = jax.random.split(key, 3)
    s1 = 1.0 / jnp.sqrt(H)
    s2 = 1.0 / jnp.sqrt(Dh)
    return {
        "encoder": encoder_module(cfg["encoder"]).init(k_enc, d_in, H),
        "head_w1": (jax.random.normal(k1, (H, Dh)) * s1).astype(jnp.float32),
        "head_b1": jnp.zeros((Dh,), jnp.float32),
        "head_w2": (jax.random.normal(k2, (Dh, n_out)) * s2 * 0.1).astype(jnp.float32),
        "head_b2": jnp.zeros((n_out,), jnp.float32),
    }


def admission_params(service_seed: int, streams, cfg: dict) -> dict:
    """Cold-start models of ``streams``: key(seed) folded with 1000 + stream."""
    base = jax.random.key(service_seed)
    keys = jax.vmap(lambda s: jax.random.fold_in(base, 1000 + s))(jnp.asarray(streams))
    return jax.vmap(lambda k: init_params(k, cfg))(keys)


def buffer_stats(history: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Admission statistics of one history [L, n] (float64): mean, scale."""
    mean = history.mean(axis=0)
    std = history.std(axis=0)
    return mean, np.where(std < STD_EPS, 1.0, std)


def forward(p: dict, yw, uw, mm, cfg: dict):
    """Windows -> per-window coefficients [B, n_terms, n]."""
    xs = jnp.concatenate([yw, uw], axis=-1) if uw.shape[-1] else yw
    h = encoder_module(cfg["encoder"]).encode(p["encoder"], xs, mm, cfg)
    h = h * jax.lax.rsqrt(jnp.mean(jnp.square(h), axis=-1, keepdims=True) + RMS_EPS)
    z = jax.nn.relu(mm(h, p["head_w1"]) + p["head_b1"])
    out = mm(z, p["head_w2"]) + p["head_b2"]
    n = cfg["state_dim"]
    return out.reshape(out.shape[0], -1, n)


def reconstruct(theta, yw, uw, mm, cfg: dict):
    """RK4 of dy/dt = clip(library([y, u]) @ theta, +-100) from each window's
    first sample, the input held over each step."""
    T = yw.shape[1]
    dts = jnp.diff(jnp.arange(T, dtype=jnp.float32) * cfg["dt"])

    def one(y0, us, th):
        def f(y, u):
            z = jnp.concatenate([y, u]) if u.shape[-1] else y
            return jnp.clip(mm(library(z, cfg["order"])[None], th)[0], -100.0, 100.0)

        def step(y, inp):
            dt, u = inp
            k1 = f(y, u)
            k2 = f(y + 0.5 * dt * k1, u)
            k3 = f(y + 0.5 * dt * k2, u)
            k4 = f(y + dt * k3, u)
            y = y + (dt / 6.0) * (k1 + 2 * k2 + 2 * k3 + k4)
            return y, y

        _, ys = jax.lax.scan(step, y0, (dts, us[:-1]))
        return jnp.concatenate([y0[None], ys], axis=0)

    return jax.vmap(one)(yw[:, 0], uw, theta)


def loss_fn(p, yw, uw, mm, cfg: dict, keep: float):
    theta = forward(p, yw, uw, mm, cfg)
    n = max(1, int(round(yw.shape[0] * keep)))
    y_est = reconstruct(theta[:n], yw[:n], uw[:n], mm, cfg)
    recon = jnp.mean((y_est - yw[:n]) ** 2)
    sparse = jnp.mean(jnp.abs(theta[:n]))
    return cfg["recon_weight"] * recon + cfg["lambda_sparse"] * sparse, recon


def windows(buf, window: int, stride: int):
    n_win = (buf.shape[0] - window) // stride + 1
    idx = (np.arange(n_win) * stride)[:, None] + np.arange(window)[None, :]
    return buf[idx]


def _leaf_norms(tree) -> jnp.ndarray:
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x))) for x in jax.tree.leaves(tree)])


@functools.partial(jax.jit, static_argnames=("cfg_items", "K", "precision", "keep"))
def replay(params, mean, scale, hist_y, hist_u, chunks_y, chunks_u, *, cfg_items, K, precision,
           keep=1.0):
    """One slot's life from admission, ``n_ticks = chunks_y.shape[0]`` ticks.

    Returns per tick: theta [n_ticks, n_terms, n], the last step's
    reconstruction MSE, delta, the AdamW first moment's per-leaf norms, the
    per-leaf norms of the change from the admitted params, and the per-leaf
    norms of the tick's first (clipped) gradient.
    """
    cfg = dict(cfg_items)
    mm = PRECISIONS[precision]
    L, C = hist_y.shape[0], chunks_y.shape[1]
    zeros = jax.tree.map(jnp.zeros_like, params)
    p0 = params
    n_th = n_terms(cfg["state_dim"] + cfg["input_dim"], cfg["order"])

    def tick(carry, inp):
        p, m, v, count, buf_y, buf_u, theta, delta, steps = carry
        cy, cu = inp
        buf_y = jnp.concatenate([buf_y[C:], cy])
        buf_u = jnp.concatenate([buf_u[C:], cu])
        yw = windows((buf_y - mean) / scale, cfg["window"], cfg["stride"])
        uw = windows(buf_u, cfg["window"], cfg["stride"])
        recon = jnp.zeros((), jnp.float32)
        g_norms = jnp.zeros((len(jax.tree.leaves(p)),), jnp.float32)
        if K:

            def step(c, j):
                p, m, v, count = c
                (_, rec), g = jax.value_and_grad(
                    lambda q: loss_fn(q, yw, uw, mm, cfg, keep), has_aux=True)(p)
                gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
                g = jax.tree.map(lambda x: x * jnp.minimum(1.0, CLIP / (gnorm + 1e-12)), g)
                frac = (steps + j + 1.0) / WARMUP
                lr = cfg["lr"] * jnp.minimum(frac, jax.lax.rsqrt(frac))
                count = count + 1
                bc1 = 1.0 - BETA1 ** count.astype(jnp.float32)
                bc2 = 1.0 - BETA2 ** count.astype(jnp.float32)
                m = jax.tree.map(lambda m_, g_: BETA1 * m_ + (1.0 - BETA1) * g_, m, g)
                v = jax.tree.map(lambda v_, g_: BETA2 * v_ + (1.0 - BETA2) * g_ * g_, v, g)
                p = jax.tree.map(
                    lambda p_, m_, v_: p_ - lr * ((m_ / bc1) / (jnp.sqrt(v_ / bc2) + ADAM_EPS)
                                                  + WEIGHT_DECAY * p_),
                    p, m, v)
                return (p, m, v, count), (rec, _leaf_norms(g))

            (p, m, v, count), (recs, gns) = jax.lax.scan(step, (p, m, v, count), jnp.arange(K))
            recon, g_norms = recs[-1], gns[0]
        raw = forward(p, yw, uw, mm, cfg).mean(axis=0)
        seed = (steps == 0) & jnp.isinf(delta)
        new = jnp.where(seed, raw, cfg["ema"] * theta + (1.0 - cfg["ema"]) * raw)
        delta = jnp.max(jnp.abs(new - theta)) / (jnp.max(jnp.abs(new)) + 1e-3)
        change = _leaf_norms(jax.tree.map(lambda a, b: a - b, p, p0))
        out = (new, recon, delta, _leaf_norms(m), change, g_norms)
        return (p, m, v, count, buf_y, buf_u, new, delta, steps + K), out

    carry = (params, zeros, zeros, jnp.zeros((), jnp.int32), hist_y, hist_u,
             jnp.zeros((n_th, cfg["state_dim"]), jnp.float32), jnp.asarray(jnp.inf),
             jnp.zeros((), jnp.int32))
    _, outs = jax.lax.scan(tick, carry, (chunks_y, chunks_u))
    return outs


def replay_many(params, means, scales, hist_y, hist_u, chunks_y, chunks_u, cfg: dict, K: int,
                precision: str = "highest", keep: float = 1.0):
    """``replay`` over a batch of slots (leading axis), run at ``precision``."""
    items = tuple(sorted((k, v) for k, v in cfg.items() if isinstance(v, (int, float, str))))
    fn = jax.vmap(functools.partial(replay, cfg_items=items, K=K, precision=precision, keep=keep))
    with jax.default_matmul_precision("highest"):
        outs = fn(params, jnp.asarray(means, jnp.float32), jnp.asarray(scales, jnp.float32),
                  jnp.asarray(hist_y), jnp.asarray(hist_u), jnp.asarray(chunks_y),
                  jnp.asarray(chunks_u))
    return jax.tree.map(np.asarray, outs)
