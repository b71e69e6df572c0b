"""Plain reference for dense decoder-only GQA models with LoRA on q, k,
v and o (InternLM2, Qwen3 and their kin), in ``jax.numpy``.

It follows the published equations and imports nothing of the program:

    h = x + o(attn(rope(norm_q(q(n1(x)))), rope(norm_k(k(n1(x)))), v(n1(x))))
    y = h + down(silu(gate(n2(h))) * up(n2(h)))
    logits = head(n_f(y))

with RMSNorm ``x / sqrt(mean(x^2) + eps) * g``, the per-head q/k RMSNorm
only where the configuration has it (Qwen3), rotate-half RoPE with the
configuration's theta, causal softmax attention scaled by head_dim^-0.5
with each group of query heads sharing one KV head, and each adapted
projection ``x W + (x A) B`` at the adapter's own rank (its arrays are
zero past the rank, so the padded columns add nothing).

No kernels, no cache, no batching: one sequence, every position, the
layers in a scan. ``dtype`` float32 runs every matrix product at
``HIGHEST`` precision; a control runs the same equations with weights
and activations in bfloat16 at default precision.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def _rms(x, g, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def _rope(x, pos, theta):
    """x: (T, H, Dh), rotate-half convention."""
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(
        jnp.float32)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           -1).astype(x.dtype)


def weight_shapes(d) -> dict[str, tuple]:
    """The weights the harness makes, under the program's flat names."""
    L, D, F = d.n_layers, d.d_model, d.d_ff
    s = {"embed/tok": (d.vocab, D), "final_norm": (D,),
         "layers/attn_norm": (L, D), "layers/q": (L, D, d.q_dim),
         "layers/k": (L, D, d.kv_dim), "layers/v": (L, D, d.kv_dim),
         "layers/o": (L, d.q_dim, D), "layers/mlp_norm": (L, D),
         "layers/gate": (L, D, F), "layers/up": (L, D, F),
         "layers/down": (L, F, D)}
    if not d.tied:
        s["lm_head"] = (D, d.vocab)
    if d.qk_norm:
        s["layers/q_norm"] = (L, d.head_dim)
        s["layers/k_norm"] = (L, d.head_dim)
    return s


def matmul_params(d, head: bool) -> int:
    """Weights a token multiplies through: q, k, v, o, gated MLP, head."""
    per_layer = (d.d_model * (d.q_dim + 2 * d.kv_dim) + d.q_dim * d.d_model
                 + 3 * d.d_model * d.d_ff)
    return d.n_layers * per_layer + (d.d_model * d.vocab if head else 0)


@partial(jax.jit, static_argnames=("dims", "dtype"))
def logits_at(w, adapter, tokens, pos, *, dims, dtype):
    """Logits (N, V) at positions ``pos`` (N,) of ``tokens`` (T,).

    ``w``: the weights by the program's flat names; ``adapter``:
    {target: (A (L, d_in, r), B (L, r, d_out))}; ``dims``: a hashable
    record of the sizes (``bench.harness.model.Dims``)."""
    d = dims
    prec = (jax.lax.Precision.HIGHEST if dtype == "float32"
            else jax.lax.Precision.DEFAULT)
    cast = lambda t: jax.tree.map(lambda a: a.astype(dtype), t)  # noqa
    w, adapter = cast(w), cast(adapter)
    mm = partial(jnp.einsum, precision=prec)
    T = tokens.shape[0]
    G = d.n_heads // d.n_kv_heads
    posn = jnp.arange(T)
    causal = posn[None, :] <= posn[:, None]

    def proj(x, name, p, lo):
        a, b = lo[name]
        return mm("td,de->te", x, p[name]) + mm(
            "tr,re->te", mm("td,dr->tr", x, a), b)

    def layer(x, xs):
        p, lo = xs
        h = _rms(x, p["attn_norm"], d.norm_eps)
        q = proj(h, "q", p, lo).reshape(T, d.n_heads, d.head_dim)
        k = proj(h, "k", p, lo).reshape(T, d.n_kv_heads, d.head_dim)
        v = proj(h, "v", p, lo).reshape(T, d.n_kv_heads, d.head_dim)
        if d.qk_norm:
            q = _rms(q, p["q_norm"], d.norm_eps)
            k = _rms(k, p["k_norm"], d.norm_eps)
        q, k = _rope(q, posn, d.rope_theta), _rope(k, posn, d.rope_theta)
        qg = q.reshape(T, d.n_kv_heads, G, d.head_dim).transpose(1, 2, 0, 3)

        def head_group(args):          # one KV head and its G queries
            qh, kh, vh = args
            s = mm("gqd,sd->gqs", qh, kh) * d.head_dim ** -0.5
            s = jnp.where(causal[None], s, -jnp.inf)
            pr = jax.nn.softmax(s.astype(jnp.float32), -1).astype(dtype)
            return mm("gqs,sd->gqd", pr, vh)

        att = jax.lax.map(head_group, (qg, k.transpose(1, 0, 2),
                                       v.transpose(1, 0, 2)))
        att = att.transpose(2, 0, 1, 3).reshape(T, d.q_dim)
        x = x + proj(att, "o", p, lo)
        h = _rms(x, p["mlp_norm"], d.norm_eps)
        f = jax.nn.silu(mm("td,df->tf", h, p["gate"])) * mm(
            "td,df->tf", h, p["up"])
        return x + mm("tf,fd->td", f, p["down"]), None

    layers = {k.split("/", 1)[1]: v for k, v in w.items()
              if k.startswith("layers/")}
    x = w["embed/tok"][tokens]
    x, _ = jax.lax.scan(layer, x, (layers, adapter))
    y = _rms(x[pos], w["final_norm"], d.norm_eps)
    head = w["embed/tok"].T if d.tied else w["lm_head"]
    return mm("nd,dv->nv", y, head).astype(jnp.float32)
