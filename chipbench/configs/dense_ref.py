"""Plain float32 reference of a dense decoder serving requests over a
shared corpus with MoSKA's routed shared-KV attention.

It follows the published layer equations (pre-norm RMSNorm, RoPE on the
two halves of each head, grouped-query attention, SwiGLU MLP, optional
q/k/v biases, tied or untied LM head) and MoSKA's attention rule as stated
in the paper: the corpus is prefilled once, causally, at positions
0..N-1 and cut into chunks; a chunk's router embedding is the mean of its
keys; each query group scores every chunk by the dot product of its
(pooled) query with the chunk embedding, summed over heads, and attends,
in one softmax, to the keys of its top-k chunks and to its own causal
prefix. A prompt's queries are routed in blocks of 128 positions, pooled
over the prompt's own tokens; every generated position is routed alone.

Nothing here comes from the program: the weights are drawn again, a layer
at a time, by ``chipbench.weights``; every product runs in float32 at
``Precision.HIGHEST``. Work proceeds layer by layer over the corpus and
the requests together, in blocks of rows, so that one layer's weights and
activations are all that is held.

``lowp=True`` is the control: the same computation with every matrix
product's operands, and the keys and values, rounded to the precision one
step below the configuration's -- float8 (e4m3, with a scale per row or
per output column) for bfloat16, bfloat16 for float32.
"""
from __future__ import annotations

import math
from functools import partial
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights as W

HI = jax.lax.Precision.HIGHEST
ROUTE_BLOCK = 128
F8_MAX = 448.0


def _q8(x, axis):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.maximum(amax, 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _lower(x, axis, lowp):
    """``x`` rounded to the control's precision (``lowp``: the name of the
    configuration's dtype, or False for the reference itself)."""
    if not lowp:
        return x
    if lowp == "float32":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    return _q8(x, axis)


def _mm(x, w, lowp):
    return jnp.matmul(_lower(x, -1, lowp), _lower(w, 0, lowp), precision=HI)


def _norm(x, s, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + s)


def _rope(x, pos, theta):
    """x (..., S, heads, D); pos (S,)."""
    D = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _rows(fn, x, blk):
    """Apply ``fn`` to blocks of ``blk`` rows of ``x`` (rows on axis 0)."""
    n = x.shape[0]
    blk = math.gcd(n, blk)
    y = jax.lax.map(fn, x.reshape((n // blk, blk) + x.shape[1:]))
    return jax.tree.map(lambda a: a.reshape((n,) + a.shape[2:]), y)


def _qkv(lw, x, m, lowp):
    H, KH, D = m["H"], m["KH"], m["D"]
    q, k, v = (_mm(x, lw[n], lowp) for n in ("wq", "wk", "wv"))
    if m["bias"]:
        q, k, v = q + lw["bq"], k + lw["bk"], v + lw["bv"]
    return (q.reshape(x.shape[:-1] + (H, D)),
            k.reshape(x.shape[:-1] + (KH, D)),
            v.reshape(x.shape[:-1] + (KH, D)))


def _mlp(lw, x, lowp):
    g = _mm(x, lw["w_gate"], lowp)
    u = _mm(x, lw["w_up"], lowp)
    return _mm(jax.nn.silu(g) * u, lw["w_down"], lowp)


def _post(lw, h, o, m, eps, lowp):
    """Residual adds of the attention output and the MLP, row-blocked."""
    def f(args):
        hb, ob = args
        hb = hb + _mm(ob.reshape(ob.shape[0], -1), lw["wo"], lowp)
        return hb + _mlp(lw, _norm(hb, lw["post_norm"], eps), lowp)
    n = h.shape[0]
    blk = math.gcd(n, 1024)
    y = jax.lax.map(f, (h.reshape(n // blk, blk, -1),
                        o.reshape((n // blk, blk) + o.shape[1:])))
    return y.reshape(h.shape)


def _causal_attention(q, k, v, bq):
    """Exact causal softmax attention of N tokens, by blocks of ``bq``
    queries against the key blocks up to the diagonal.
    q (N, H, D); k, v (N, KH, D) -> (N, H, D)."""
    N, H, D = q.shape
    KH = k.shape[1]
    G = H // KH
    scale = 1.0 / math.sqrt(D)
    qs = q.reshape(N // bq, bq, KH, G, D)

    def one(args):
        qi, qb = args

        def body(kj, carry):
            mx, l, acc = carry
            kb = jax.lax.dynamic_slice_in_dim(k, kj * bq, bq)
            vb = jax.lax.dynamic_slice_in_dim(v, kj * bq, bq)
            s = jnp.einsum("qkgd,jkd->kgqj", qb, kb, precision=HI) * scale
            causal = (kj * bq + jnp.arange(bq))[None, :] <= \
                (qi * bq + jnp.arange(bq))[:, None]
            s = jnp.where(causal, s, -jnp.inf)
            mn = jnp.maximum(mx, s.max(-1))
            p = jnp.exp(s - mn[..., None])
            c = jnp.exp(mx - mn)
            l = l * c + p.sum(-1)
            acc = acc * c[..., None] + jnp.einsum("kgqj,jkd->kgqd", p, vb,
                                                  precision=HI)
            return mn, l, acc

        init = (jnp.full((KH, G, bq), -jnp.inf), jnp.zeros((KH, G, bq)),
                jnp.zeros((KH, G, bq, D)))
        mx, l, acc = jax.lax.fori_loop(0, qi + 1, body, init)
        return (acc / l[..., None]).transpose(2, 0, 1, 3).reshape(bq, H, D)

    o = jax.lax.map(one, (jnp.arange(N // bq), qs))
    return o.reshape(N, H, D)


@partial(jax.jit, static_argnames=("m", "eps", "theta", "lowp", "bq"))
def _corpus_layer(lw, h, *, m, eps, theta, lowp, bq):
    m = dict(m)
    N = h.shape[0]
    pos = jnp.arange(N)
    q, k, v = _rows(lambda x: _qkv(lw, _norm(x, lw["input_norm"], eps), m,
                                   lowp), h, 1024)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    k, v = _lower(k, -1, lowp), _lower(v, -1, lowp)
    o = _causal_attention(q, k, v, bq)
    return _post(lw, h, o, m, eps, lowp), k, v


@partial(jax.jit, static_argnames=("m", "eps", "theta", "lowp", "top_k",
                                   "chunk"))
def _request_layer(lw, h, kc, vc, plen, *, m, eps, theta, lowp, top_k, chunk):
    """h (R, S, d) request rows; kc, vc (N, KH, D) this layer's corpus keys
    and values (N may be 0); plen (R,) prompt lengths."""
    m = dict(m)
    R, S, _ = h.shape
    H, KH, D = m["H"], m["KH"], m["D"]
    G = H // KH
    N = kc.shape[0]
    scale = 1.0 / math.sqrt(D)
    pos = N + jnp.arange(S)
    q, k, v = _qkv(lw, _norm(h, lw["input_norm"], eps), m, lowp)
    q = jax.vmap(lambda x: _rope(x, pos, theta))(q)
    k = jax.vmap(lambda x: _rope(x, pos, theta))(k)
    k, v = _lower(k, -1, lowp), _lower(v, -1, lowp)
    i = jnp.arange(S)
    nb = S // ROUTE_BLOCK
    if N:
        E = N // chunk
        emb = kc.reshape(E, chunk, KH, D).mean(axis=1)           # (E, KH, D)
        inp = (i[None, :] < plen[:, None]).astype(jnp.float32)  # (R, S)
        qp = (q * inp[..., None, None]).reshape(R, nb, ROUTE_BLOCK, H, D)
        cnt = inp.reshape(R, nb, ROUTE_BLOCK).sum(-1)
        pooled = qp.sum(2) / jnp.maximum(cnt, 1.0)[..., None, None]
        rq = jnp.where(inp[..., None, None] > 0,
                       pooled[:, i // ROUTE_BLOCK], q)            # (R,S,H,D)
        score = jnp.einsum("rskgd,ekd->rse", rq.reshape(R, S, KH, G, D),
                           emb, precision=HI) * scale
        _, ids = jax.lax.top_k(score, top_k)
        sel = jax.nn.one_hot(ids, E, dtype=jnp.bool_).any(-2)    # (R,S,E)
    else:
        sel = jnp.zeros((R, S, 1), bool)
    t_chunk = jnp.arange(N) // chunk

    def block(args):
        r, b = args
        qb = jax.lax.dynamic_slice_in_dim(q[r], b * ROUTE_BLOCK, ROUTE_BLOCK)
        qb = qb.reshape(ROUTE_BLOCK, KH, G, D)
        qi = b * ROUTE_BLOCK + jnp.arange(ROUTE_BLOCK)
        su = jnp.einsum("qkgd,jkd->kgqj", qb, k[r], precision=HI) * scale
        su = jnp.where(i[None, :] <= qi[:, None], su, -jnp.inf)
        mx = su.max(-1)
        if N:
            sb = jax.lax.dynamic_slice_in_dim(sel[r], b * ROUTE_BLOCK,
                                              ROUTE_BLOCK)        # (Q, E)
            ss = jnp.einsum("qkgd,tkd->kgqt", qb, kc, precision=HI) * scale
            ss = jnp.where(sb[:, t_chunk], ss, -jnp.inf)
            mx = jnp.maximum(mx, ss.max(-1))
        pu = jnp.exp(su - mx[..., None])
        l = pu.sum(-1)
        o = jnp.einsum("kgqj,jkd->kgqd", pu, v[r], precision=HI)
        if N:
            ps = jnp.exp(ss - mx[..., None])
            l = l + ps.sum(-1)
            o = o + jnp.einsum("kgqt,tkd->kgqd", ps, vc, precision=HI)
        o = o / l[..., None]
        return o.transpose(2, 0, 1, 3).reshape(ROUTE_BLOCK, H, D)

    rr, bb = jnp.meshgrid(jnp.arange(R), jnp.arange(nb), indexing="ij")
    o = jax.lax.map(block, (rr.reshape(-1), bb.reshape(-1)))
    o = o.reshape(R * S, H, D)
    h = _post(lw, h.reshape(R * S, -1), o, m, eps, lowp)
    return h.reshape(R, S, -1)


@partial(jax.jit, static_argnames=("eps", "lowp", "tied"))
def _logits(gw, x, *, eps, lowp, tied):
    x = _norm(x, gw["final_norm"], eps)
    w = gw["embed"] if tied else gw["unembed"]
    return _mm(x, w.T, lowp)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def logits(conf: dict, seed: int, corpus: np.ndarray,
           seqs: Sequence[Tuple[np.ndarray, np.ndarray]],
           lowp: bool = False, rows: int = 0,
           seq_len: int = 0) -> jax.Array:
    """Reference logits at every position that produced a served token.

    ``seqs``: (prompt, served tokens) per request. Returns (T, V) float32,
    the rows of each request in order: row j of a request scores its
    served token j (j = 0 comes from the prompt's last position).

    ``rows`` and ``seq_len``, where given, pad the requests to that many
    rows of that many positions, so that every run's programs have the
    same shapes and come from the compilation cache; the padding rows are
    computed and dropped, and no row attends to another.
    """
    m = W.dims(conf)
    mk = tuple(sorted(m.items()))
    eps, theta = float(conf["rms_norm_eps"]), float(conf["rope_theta"])
    chunk = conf["moska"]["chunk_size"]
    top_k = conf["moska"]["top_k_chunks"]
    dtype = conf["torch_dtype"]
    R = max(len(seqs), rows)
    fed = [np.concatenate([p, s[:-1]]).astype(np.int32) for p, s in seqs]
    S = max(max(len(f) for f in fed), seq_len)
    S = -(-S // ROUTE_BLOCK) * ROUTE_BLOCK
    toks = np.zeros((R, S), np.int32)
    for r, f in enumerate(fed):
        toks[r, :len(f)] = f
    plen = jnp.asarray([len(p) for p, _ in seqs] + [1] * (R - len(seqs)),
                       jnp.int32)
    N = len(corpus)
    lowp = dtype if lowp else False
    gw = _f32(W.globals_(seed, m, dtype))
    h = gw["embed"][jnp.asarray(toks)]
    hc = gw["embed"][jnp.asarray(corpus, jnp.int32)] if N else None
    empty = jnp.zeros((0, m["KH"], m["D"]), jnp.float32)
    bq = math.gcd(N, 1024) if N else 1
    with jax.default_matmul_precision("highest"):
        for li in range(m["L"]):
            lw = _f32(W.layer(seed, m, li, dtype))
            kc = vc = empty
            if N:
                hc, kc, vc = _corpus_layer(lw, hc, m=mk, eps=eps, theta=theta,
                                           lowp=lowp, bq=bq)
            h = _request_layer(lw, h, kc, vc, plen, m=mk, eps=eps,
                               theta=theta, lowp=lowp, top_k=top_k,
                               chunk=chunk)
            del lw, kc, vc
        rows = [h[r, len(p) - 1:len(p) - 1 + len(s)]
                for r, (p, s) in enumerate(seqs)]
        return _logits(gw, jnp.concatenate(rows), eps=eps, lowp=lowp,
                       tied=m["tied"])
