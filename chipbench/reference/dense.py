"""Plain float32 reference of a dense GQA decoder (Llama layout).

Imports nothing of the program under test.  One sequence at a time, full
causal attention materialised in float32, every matrix product at
``HIGHEST`` precision.  The same functions serve three purposes:

* ``init_params`` makes the weights the benchmark hands to the program
  (one jitted call from the seed, in the dtype the cell runs them in);
* ``loss_sums`` / ``train_steps`` follow the program's first training
  steps (cross entropy plus 1e-4 of the squared log-partition, AdamW with
  global-norm clipping);
* ``logits_at`` scores served tokens against the full forward pass.

``mode="fp8"`` casts both operands of every matrix product to float8
e4m3 with a per-tensor scale: the control, one precision step below the
bfloat16 compute the configurations state.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Z_LOSS = 1e-4
NO_DECAY = ("scale",)


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    h: int
    kh: int
    hd: int
    f: int
    layers: int
    vocab: int
    tied: bool
    eps: float
    theta: float
    window: int = 0

    @classmethod
    def from_config(cls, c: Dict[str, Any]) -> "Dims":
        return cls(d=c["hidden_size"], h=c["num_attention_heads"],
                   kh=c["num_key_value_heads"], hd=c["head_dim"],
                   f=c["intermediate_size"], layers=c["num_hidden_layers"],
                   vocab=c["vocab_size"], tied=bool(c["tie_word_embeddings"]),
                   eps=float(c["rms_norm_eps"]), theta=float(c["rope_theta"]),
                   window=int(c.get("sliding_window") or 0))


# ------------------------------------------------------------------ weights

def param_shapes(dm: Dims) -> Dict[str, Any]:
    L, d, h, kh, hd, f = dm.layers, dm.d, dm.h, dm.kh, dm.hd, dm.f
    shapes = {
        "embedding": (dm.vocab, d),
        "final_norm": {"scale": (d,)},
        "layers": {
            "ln1": {"scale": (L, d)}, "ln2": {"scale": (L, d)},
            "attn": {"w_q": (L, d, h, hd), "w_k": (L, d, kh, hd),
                     "w_v": (L, d, kh, hd), "w_o": (L, h, hd, d)},
            "mlp": {"w_gate": (L, d, f), "w_up": (L, d, f),
                    "w_down": (L, f, d)},
        },
    }
    if not dm.tied:
        shapes["lm_head"] = (d, dm.vocab)
    return shapes


def _fan_in(path: str, shape) -> int:
    if path.endswith("w_o"):
        return shape[-3] * shape[-2]
    if path == "embedding" or path == "lm_head":
        return shape[-1] if path == "embedding" else shape[0]
    return shape[-2] if path.endswith(("w_gate", "w_up", "w_down")) \
        else shape[-3]


def init_params(dm: Dims, seed32: int, dtype,
                out_shardings=None) -> Dict[str, Any]:
    """Normal weights of std 1/sqrt(fan-in), unit norms; one jitted call,
    made where ``out_shardings`` places them (one device by default)."""
    shapes = param_shapes(dm)
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    names = ["/".join(str(getattr(k, "key", k)) for k in p) for p, _ in flat]

    def make(key):
        keys = jax.random.split(key, len(flat))
        out = []
        for k, name, (_, shape) in zip(keys, names, flat):
            if name.endswith("scale"):
                out.append(jnp.ones(shape, dtype))
            else:
                w = jax.random.normal(k, shape, jnp.float32)
                out.append((w / np.sqrt(_fan_in(name, shape))).astype(dtype))
        return jax.tree_util.tree_unflatten(tree, out)

    return jax.jit(make, out_shardings=out_shardings)(
        jax.random.PRNGKey(seed32))


# --------------------------------------------------------------- arithmetic

def _quant(x, dtype, top: float):
    """Round x to ``dtype`` under one per-tensor scale, back in float32."""
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8(x):
    """Operands in float8 e4m3 going forward, their cotangents in float8
    e5m2 coming back, as float8 training computes."""
    return _quant(x, jnp.float8_e4m3fn, 448.0)


def _fp8_fwd(x):
    return _fp8(x), None


def _fp8_bwd(_, g):
    return (_quant(g, jnp.float8_e5m2, 57344.0),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def _mm(mode: str):
    if mode == "f32":
        def mm(spec, a, b):
            return jnp.einsum(spec, a.astype(jnp.float32),
                              b.astype(jnp.float32), precision=HI)
    elif mode == "fp8":
        def mm(spec, a, b):
            return jnp.einsum(spec, _fp8(a), _fp8(b), precision=HI)
    else:
        raise ValueError(mode)
    return mm


def rmsnorm(x, scale, eps):
    x = x.astype(jnp.float32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * scale.astype(jnp.float32))


def rope(x, pos, theta):
    """x (T, heads, hd); rotate-half layout."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def layer(x, p, dm: Dims, mm):
    """One pre-norm decoder layer over a whole sequence x (T, d)."""
    t = x.shape[0]
    pos = jnp.arange(t)
    g = dm.h // dm.kh
    h = rmsnorm(x, p["ln1"]["scale"], dm.eps)
    q = rope(mm("td,dhk->thk", h, p["attn"]["w_q"]), pos, dm.theta)
    k = rope(mm("td,dhk->thk", h, p["attn"]["w_k"]), pos, dm.theta)
    v = mm("td,dhk->thk", h, p["attn"]["w_v"])
    q = q.reshape(t, dm.kh, g, dm.hd)
    s = mm("qkgh,skh->kgqs", q, k) / np.sqrt(dm.hd)
    mask = pos[None, :] <= pos[:, None]
    if dm.window:
        mask &= (pos[:, None] - pos[None, :]) < dm.window
    s = jnp.where(mask, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = mm("kgqs,skh->qkgh", a, v).reshape(t, dm.h, dm.hd)
    x = x + mm("thk,hkd->td", o, p["attn"]["w_o"])
    h = rmsnorm(x, p["ln2"]["scale"], dm.eps)
    gate = mm("td,df->tf", h, p["mlp"]["w_gate"])
    up = mm("td,df->tf", h, p["mlp"]["w_up"])
    return x + mm("tf,fd->td", jax.nn.silu(gate) * up, p["mlp"]["w_down"])


def hidden(params, tokens, dm: Dims, mm, remat: bool = False):
    """Final-normed hidden states (T, d) of one sequence.  Weights are
    upcast one layer at a time inside the scan."""
    x = params["embedding"][tokens].astype(jnp.float32)
    body = (lambda xx, p_l: (layer(xx, p_l, dm, mm), None))
    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["layers"])
    return rmsnorm(x, params["final_norm"]["scale"], dm.eps)


def unembed(params, dm: Dims):
    return params["embedding"].T if dm.tied else params["lm_head"]


# ------------------------------------------------------------------ serving

def make_logits_at(dm: Dims, mode: str = "f32"):
    """jitted (params, tokens (T,), idx (n,)) -> logits (n, vocab) float32
    at positions ``idx`` of one causal pass over ``tokens``."""
    mm = _mm(mode)

    def fn(params, tokens, idx):
        h = hidden(params, tokens, dm, mm)[idx]
        return mm("nd,dv->nv", h, unembed(params, dm))

    return jax.jit(fn)


# ----------------------------------------------------------------- training

def loss_sums(params, tokens, targets, dm: Dims, mm, chunk: int = 1024):
    """(sum of cross entropy, sum of lse^2) over one row."""
    h = hidden(params, tokens, dm, mm, remat=True)
    w = unembed(params, dm)
    t = h.shape[0]
    chunk = min(chunk, t)

    def body(carry, inp):
        hc, yc = inp
        lg = mm("td,dv->tv", hc, w)
        lse = jax.scipy.special.logsumexp(lg, axis=-1)
        ll = jnp.take_along_axis(lg, yc[:, None], axis=-1)[:, 0]
        return (carry[0] + jnp.sum(lse - ll), carry[1] + jnp.sum(lse * lse)), None

    body = jax.checkpoint(body)
    (ce, z), _ = jax.lax.scan(
        body, (jnp.zeros(()), jnp.zeros(())),
        (h.reshape(t // chunk, chunk, -1), targets.reshape(t // chunk, chunk)))
    return ce, z


@dataclasses.dataclass(frozen=True)
class Adam:
    peak_lr: float
    warmup_steps: int
    total_steps: int
    min_lr_frac: float = 0.1
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.01
    clip_norm: float = 1.0

    def lr(self, step):
        step = jnp.asarray(step, jnp.float32)
        warm = self.peak_lr * step / max(self.warmup_steps, 1)
        prog = jnp.clip((step - self.warmup_steps)
                        / max(self.total_steps - self.warmup_steps, 1), 0, 1)
        cos = self.min_lr_frac + (1 - self.min_lr_frac) * 0.5 * (
            1 + jnp.cos(np.pi * prog))
        return jnp.where(step < self.warmup_steps, warm, self.peak_lr * cos)


def make_train_step(dm: Dims, adam: Adam, mode: str = "f32"):
    """jitted (params, m, v, step, tokens (B,S), targets (B,S)) ->
    (params, m, v, loss, clipped grads).  Rows run one at a time and
    their gradients are summed, so the whole batch's mean is exact."""
    mm = _mm(mode)

    def row_obj(params, tok, tgt, n):
        ce, z = loss_sums(params, tok, tgt, dm, mm)
        return (ce + Z_LOSS * z) / n, ce / n

    def fn(params, m, v, step, tokens, targets):
        n = tokens.shape[0] * tokens.shape[1]
        grad_fn = jax.value_and_grad(row_obj, has_aux=True)

        def row(acc, inp):
            (obj, _), g = grad_fn(params, inp[0], inp[1], n)
            return (jax.tree_util.tree_map(jnp.add, acc[0], g),
                    acc[1] + obj), None

        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        (g, loss), _ = jax.lax.scan(row, (zeros, jnp.zeros(())),
                                    (tokens, targets))
        gnorm = jnp.sqrt(sum(jnp.sum(x * x)
                             for x in jax.tree_util.tree_leaves(g)))
        clip = jnp.minimum(1.0, adam.clip_norm / jnp.maximum(gnorm, 1e-12))
        g = jax.tree_util.tree_map(lambda x: x * clip, g)
        t = step + 1
        lr = adam.lr(t)
        bc1 = 1 - adam.b1 ** t.astype(jnp.float32)
        bc2 = 1 - adam.b2 ** t.astype(jnp.float32)

        def upd(path, p, g_, m_, v_):
            m_ = adam.b1 * m_ + (1 - adam.b1) * g_
            v_ = adam.b2 * v_ + (1 - adam.b2) * g_ * g_
            u = (m_ / bc1) / (jnp.sqrt(v_ / bc2) + adam.eps)
            if str(getattr(path[-1], "key", path[-1])) not in NO_DECAY:
                u = u + adam.weight_decay * p
            return p - lr * u, m_, v_

        out = jax.tree_util.tree_map_with_path(upd, params, g, m, v)
        is3 = lambda x: isinstance(x, tuple) and len(x) == 3  # noqa: E731
        p2 = jax.tree_util.tree_map(lambda o: o[0], out, is_leaf=is3)
        m2 = jax.tree_util.tree_map(lambda o: o[1], out, is_leaf=is3)
        v2 = jax.tree_util.tree_map(lambda o: o[2], out, is_leaf=is3)
        return p2, m2, v2, loss, g

    return jax.jit(fn, donate_argnums=(0, 1, 2))
