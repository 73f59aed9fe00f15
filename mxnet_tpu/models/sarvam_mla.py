"""Sarvam-105B's decoder (``model_type: sarvam_mla``) as a serving step:
multi-head latent attention in its absorbed form over a pool of latent rows,
a leading dense gated FFN and then sigmoid top-k routed experts with one
shared expert, RMSNorm before both branches, YaRN rotary positions on the
rope lanes.

``serving/decode.py::LatentDecodeProgram`` builds its compiled step from
:class:`Decoder`; nothing here knows of the engine.  With d hidden, H heads,
``q = [q_n (nope) ; q_r (rope)]`` per head, a latent of ``kv_lora_rank``
(512) and one rope key for all heads, a row at position p caches

    z_p = [ RMSNorm(W_kva h)[:512] ; RoPE((W_kva h)[512:], p) ]      (576)

and a query head scores it as ``sigma * [W_UK^T q_n ; RoPE(q_r, p)] . z``;
the value is ``W_UV`` times the softmax-weighted sum of the rows' first 512
lanes.  ``W_kv_b`` (512 -> H x (nope + v)) is ``[W_UK ; W_UV]`` by head.
The two absorption products are ordinary matmuls round the kernel
(``ops/pallas_kernels.mla_attention``).

A step takes a FIXED budget of rows: rows ``[0, slots)`` are one a slot (a
decoding slot's row), the rest is the prefill chunk, in blocks of
``mla_chunk_rows()`` whose live rows share a slot.  A row with position -1
is dead: it writes to the trash page, attends nothing, picks no expert.

The expert layers are ONE chip's share under expert parallelism
(``parallel/moe.py::moe_ffn_held``): ``num_experts`` of the configuration
counts the experts HELD, from ``first_expert`` on, of the ``router_width``
the router scores; what the absent experts would add is left out.  The
selection bias is a fixed buffer in serving (zeros unless given).

The model's dict holds the published ``config.json`` keys (``hidden_size``,
``num_attention_heads``, ``qk_nope_head_dim``, ``qk_rope_head_dim``,
``kv_lora_rank``, ``v_head_dim``, ``intermediate_size``,
``moe_intermediate_size``, ``num_experts``, ``num_experts_per_tok``,
``num_shared_experts``, ``first_k_dense_replace``, ``routed_scaling_factor``,
``rms_norm_eps``, ``rope_theta``, ``rope_scaling``) and the share's
``router_width`` and ``first_expert``.
"""
import math

import numpy as np

MODEL_KEYS = ("hidden_size", "num_attention_heads", "qk_nope_head_dim",
              "qk_rope_head_dim", "kv_lora_rank", "v_head_dim",
              "intermediate_size", "moe_intermediate_size", "num_experts",
              "num_experts_per_tok", "num_shared_experts",
              "first_k_dense_replace", "routed_scaling_factor",
              "rms_norm_eps", "rope_theta", "rope_scaling", "router_width",
              "first_expert")


def model_of(cfg: dict) -> dict:
    """The keys this block reads, out of a configuration that holds more."""
    out = {k: cfg[k] for k in MODEL_KEYS if k in cfg}
    out.setdefault("router_width", out["num_experts"])
    out.setdefault("first_expert", 0)
    return out


def yarn_inv_freq(model: dict) -> np.ndarray:
    """Rotary inverse frequencies of the rope lanes under
    ``deepseek_yarn``: (rope / 2,) float32.  Pair j turns by
    ``position * inv_freq[j]``; the pairs whose wavelength the original
    context held many times keep their frequency, those it held less than
    once are slowed by ``factor``, a linear ramp between."""
    dim = model["qk_rope_head_dim"]
    theta = float(model["rope_theta"])
    freq = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    rs = model.get("rope_scaling")
    if not rs:
        return freq.astype(np.float32)
    if rs["type"] != "deepseek_yarn":
        raise ValueError("rope_scaling type %r is not deepseek_yarn"
                         % (rs["type"],))

    def corr(turns):
        return dim * math.log(rs["original_max_position_embeddings"]
                              / (turns * 2 * math.pi)) / (2 * math.log(theta))

    lo = max(math.floor(corr(rs["beta_fast"])), 0)
    hi = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - lo)
                   / max(hi - lo, 1e-3), 0.0, 1.0)
    keep = 1.0 - ramp
    return ((1.0 - keep) * freq / rs["factor"] + keep * freq) \
        .astype(np.float32)


def softmax_scale(model: dict) -> float:
    """``q_head_dim ** -0.5``, times ``mscale ** 2`` where YaRN's
    ``mscale_all_dim`` is set (m = 0.1 * mscale_all_dim * ln(factor) + 1)."""
    scale = (model["qk_nope_head_dim"] + model["qk_rope_head_dim"]) ** -0.5
    rs = model.get("rope_scaling") or {}
    if rs.get("mscale_all_dim") and rs.get("factor", 1) > 1:
        m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
        scale *= m * m
    return float(scale)


def is_expert_layer(model: dict, i: int) -> bool:
    return i >= model["first_k_dense_replace"]


def param_shapes(model: dict, num_layers: int, vocab_size: int) -> dict:
    """Name -> shape of every parameter the step consumes.  ``*_gamma`` and
    ``*_expert_bias`` are float32, the rest the serving dtype."""
    d, H = model["hidden_size"], model["num_attention_heads"]
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    lat, v = model["kv_lora_rank"], model["v_head_dim"]
    F, f = model["intermediate_size"], model["moe_intermediate_size"]
    held, E = model["num_experts"], model["router_width"]
    fs = f * model["num_shared_experts"]
    shapes = {"tok_embed_weight": (vocab_size, d)}
    for i in range(num_layers):
        p = "l%d_" % i
        shapes.update({
            p + "ln1_gamma": (d,), p + "q_weight": (H * (nope + rope), d),
            p + "kva_weight": (lat + rope, d), p + "kvn_gamma": (lat,),
            p + "kvb_weight": (H * (nope + v), lat),
            p + "proj_weight": (d, H * v), p + "ln2_gamma": (d,)})
        if is_expert_layer(model, i):
            p += "moe_"
            shapes.update({
                p + "router_weight": (d, E), p + "shared_w1": (d, fs),
                p + "shared_w3": (d, fs), p + "shared_w2": (fs, d),
                p + "expert_w1": (held, d, f), p + "expert_w3": (held, d, f),
                p + "expert_w2": (held, f, d)})
        else:
            shapes.update({p + "ff1_weight": (F, d), p + "ff3_weight": (F, d),
                           p + "ff2_weight": (d, F)})
    shapes.update({"ln_f_gamma": (d,), "head_weight": (vocab_size, d)})
    return shapes


def is_float32_param(name: str) -> bool:
    return name.endswith(("_gamma", "_expert_bias"))


def serving_row_buckets(rows: int, model: dict, multiple: int = 256):
    """``parallel.moe.row_buckets`` for a step of ``rows`` rows, each budget
    rounded up to ``multiple`` rows so that the grouped products run on whole
    row tiles (a budget of 720 rows would be walked 16 rows at a time, each
    visit reading an expert's matrices again)."""
    from ..parallel.moe import row_buckets
    k, held = model["num_experts_per_tok"], model["num_experts"]
    worst = rows * min(k, held)
    return tuple(sorted({min(-(-b // multiple) * multiple, worst)
                         for b in row_buckets(rows, k, held,
                                              model["router_width"])}))


class Decoder:
    """The step's mathematics for one geometry: ``slots`` decode rows and
    ``chunk_rows`` prefill rows a step, ``dtype`` parameters and pool."""

    def __init__(self, model: dict, *, num_layers: int, vocab_size: int,
                 slots: int, chunk_rows: int, dtype):
        import jax.numpy as jnp
        self.model = model = model_of(model)
        self.num_layers, self.vocab_size = int(num_layers), int(vocab_size)
        self.slots, self.chunk_rows = int(slots), int(chunk_rows)
        self.dtype = jnp.dtype(dtype)
        self.latent = model["kv_lora_rank"]
        self.row_width = self.latent + model["qk_rope_head_dim"]
        self.scale = softmax_scale(model)
        self.inv_freq = yarn_inv_freq(model)
        self.buckets = serving_row_buckets(self.slots + self.chunk_rows,
                                           model)

    # -- pieces -------------------------------------------------------------
    def _rms(self, x, gain):
        import jax
        import jax.numpy as jnp
        x32 = x.astype(jnp.float32)
        inv = jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True)
                            + self.model["rms_norm_eps"])
        return (x32 * inv * gain).astype(self.dtype)

    def _rope(self, x, positions):
        """``x`` (R, ..., rope) turned by its row's position, pairs
        (j, j + rope / 2), in float32."""
        import jax.numpy as jnp
        ang = positions.astype(jnp.float32)[:, None] * self.inv_freq
        ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        x32 = x.astype(jnp.float32)
        a, b = jnp.split(x32, 2, axis=-1)
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                               axis=-1).astype(self.dtype)

    @staticmethod
    def _gated_ffn(x, w1, w3, w2):
        """``(silu(x W1^T) * (x W3^T)) W2^T`` for (out, in) matrices."""
        import jax
        import jax.numpy as jnp
        gate = jax.nn.silu((x @ w1.T).astype(jnp.float32))
        return (gate * (x @ w3.T).astype(jnp.float32)).astype(x.dtype) @ w2.T

    def _attention(self, p, pfx, i, x, pool, rows, use_pallas):
        import jax
        import jax.numpy as jnp
        from ..ops import pallas_kernels as pk
        m = self.model
        H, nope = m["num_attention_heads"], m["qk_nope_head_dim"]
        rope, v, lat = m["qk_rope_head_dim"], m["v_head_dim"], self.latent
        R = x.shape[0]
        scope = jax.named_scope
        with scope("mx.decode.norm"):
            h = self._rms(x, p[pfx + "ln1_gamma"])
        with scope("mx.decode.q"):
            q = (h @ p[pfx + "q_weight"].T).reshape(R, H, nope + rope)
            q_r = self._rope(q[..., nope:], rows["pos"])
        with scope("mx.decode.kv_a"):
            ckv = h @ p[pfx + "kva_weight"].T               # (R, lat + rope)
            z = jnp.concatenate(
                [self._rms(ckv[:, :lat], p[pfx + "kvn_gamma"]),
                 self._rope(ckv[:, lat:], rows["pos"])], axis=-1)
        # the pool is touched by these two and by nothing else
        with scope("mx.decode.kv_write"):
            pool = pk.latent_write(pool, i, z, rows["phys"], rows["off"],
                                   use_pallas=use_pallas)
        with scope("mx.decode.attn"):
            w_kvb = p[pfx + "kvb_weight"].reshape(H, nope + v, lat)
            q_t = jnp.einsum("rhn,hnc->rhc", q[..., :nope], w_kvb[:, :nope])
            u = pk.mla_attention(
                jnp.concatenate([q_t, q_r], axis=-1),
                pool, i, rows["page_table"], rows["slot"], rows["limit"],
                n_decode=self.slots, latent=lat, scale=self.scale,
                use_pallas=use_pallas)
            o = jnp.einsum("rhc,hvc->rhv", u, w_kvb[:, nope:])
        with scope("mx.decode.proj"):
            x = x + o.reshape(R, H * v) @ p[pfx + "proj_weight"].T
        return x, pool

    def _experts(self, p, pfx, h, live):
        import jax.numpy as jnp
        from ..parallel.moe import moe_ffn_held
        m = self.model
        E, first, held = m["router_width"], m["first_expert"], \
            m["num_experts"]
        bias = p.get(pfx + "expert_bias")
        if bias is None:
            bias = jnp.zeros((E,), jnp.float32)
        shared = (tuple(p[pfx + "shared_" + w] for w in ("w1", "w3", "w2"))
                  if m["num_shared_experts"] else None)
        out, load = moe_ffn_held(
            h, p[pfx + "router_weight"], bias, shared,
            tuple(p[pfx + "expert_" + w] for w in ("w1", "w3", "w2")),
            num_experts=E, first_expert=first,
            top_k=m["num_experts_per_tok"], route_norm=True,
            route_scale=m["routed_scaling_factor"], buckets=self.buckets,
            live=live)
        here = load[first:first + held]
        return out, jnp.stack([jnp.sum(here), jnp.sum(here > 0)]) \
            .astype(jnp.int32)

    # -- the step -------------------------------------------------------------
    def step(self, p, pool, tokens, positions, seq_lens, phys, off,
             page_table, prev_tok, row_slot, out_row, use_pallas=None):
        """One step over the fixed budget of rows.  Per row (R,): ``tokens``
        (negative: the token the last step produced for the row's slot,
        ``prev_tok[row_slot]``), ``positions`` (-1: a dead row), ``phys`` /
        ``off`` (where its latent row goes), ``row_slot``.  Per slot (S,):
        ``seq_lens`` (the cache positions its rows attend, this step's
        included), ``out_row`` (the row whose hidden state gives the slot's
        next token).  Returns ``(next_tokens (S,), logits (S, V) float32,
        pool, [held picks, experts touched] summed over the expert
        layers)``."""
        import jax
        import jax.numpy as jnp
        S = self.slots
        scope = jax.named_scope
        live = positions >= 0
        # a decoding slot's one row attends what seq_lens says, as the
        # one-token step's does; a chunk row, up to its own position
        limit = jnp.where(live, positions + 1, 0).astype(jnp.int32)
        limit = limit.at[:S].set(jnp.where(live[:S], seq_lens, 0))
        rows = {"pos": jnp.maximum(positions, 0), "phys": phys, "off": off,
                "slot": row_slot, "limit": limit, "page_table": page_table}
        with scope("mx.decode.embed"):
            tokens = jnp.where(tokens < 0, prev_tok[row_slot], tokens)
            x = p["tok_embed_weight"][tokens]
        counts = jnp.zeros((2,), jnp.int32)
        for i in range(self.num_layers):
            pfx = "l%d_" % i
            x, pool = self._attention(p, pfx, i, x, pool, rows, use_pallas)
            with scope("mx.decode.norm"):
                h = self._rms(x, p[pfx + "ln2_gamma"])
            if is_expert_layer(self.model, i):
                with scope("mx.decode.moe"):
                    out, seen = self._experts(p, pfx + "moe_", h, live)
                    x = x + out
                    counts = counts + seen
            else:
                with scope("mx.decode.mlp"):
                    x = x + self._gated_ffn(
                        h, p[pfx + "ff1_weight"], p[pfx + "ff3_weight"],
                        p[pfx + "ff2_weight"])
        with scope("mx.decode.norm"):
            last = self._rms(x[out_row], p["ln_f_gamma"])       # (S, d)
        with scope("mx.decode.head"):
            logits = jnp.dot(last, p["head_weight"].T,
                             preferred_element_type=jnp.float32)
        with scope("mx.decode.sample"):
            next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, logits, pool, counts
