"""LFM2-MoE's decoder (``model_type: lfm2_moe``) as a serving step: gated
short convolutions beside grouped-query attention, leading dense gated FFNs
and then sigmoid top-k routed experts, RMSNorm before every branch, the head
tied to the embedding.

``serving/decode.py::HybridDecodeProgram`` builds its compiled step from
:class:`Decoder`; nothing here knows of the engine.  Layer i is one of
``layer_types``: ``conv`` or ``full_attention``.  With ``RMS(x) = x /
sqrt(mean(x^2) + eps) * g``, a layer computes ``h = x + Mixer(RMS(x))`` and
``x' = h + FFN(RMS(h))``, where

    conv:  [B ; C ; X] = W_in u;  v_t = B_t * X_t
           z_t = sum_{k < L} w[:, k] * v_{t - (L - 1) + k}     (v_{<0} = 0)
           Mixer = W_out (C_t * z_t)
    attn:  q (H heads), k, v (H_kv heads) of width hd; RMS over a head's
           lanes on q and k; rotary positions (pairs j, j + hd / 2, theta);
           causal softmax at hd^-1/2, query head h reading head h // rep
    FFN:   layers < num_dense_layers: W2 (silu(W1 u) * W3 u); the others
           the routed experts (``parallel/moe.py::moe_ffn_held`` holding
           all of them): s = sigmoid(W_r u) in float32, the top k of s + b
           picked, weights s over their sum (``norm_topk_prob``) times
           ``routed_scaling_factor``; no shared expert

and after the stack a final RMSNorm and ``logits = u E^T``.

A step takes a FIXED budget of rows, as ``models/sarvam_mla.py``'s does:
rows ``[0, slots)`` are one a slot, the rest the prefill chunk in blocks of
``pallas_kernels.chunk_attn_rows()`` whose live rows share a slot, a slot's
chunk rows consecutive rows at consecutive positions.  A row with position
-1 is dead: it writes to the trash page and to no state, attends nothing,
picks no expert.

The step's state is a dict: ``kv``, the K/V pool of the ATTENTION layers
only ``(n_attn, 2, P, H_kv, rows, lanes)``, and ``conv``, each slot's last
``L`` values of v per convolution layer ``(n_conv, S, L, d)``, row
``p mod L`` holding position p.  A row at position p reads v at p - 1 ...
p - (L - 1) from the row before it in the same step where that row is its
slot's previous position, else from the slot's state; a position below 0
reads zeros, so a slot that starts a request reads nothing its last owner
left.  After the step each live row writes its v unless a row L further on
in the same step is its slot's position p + L: the state then holds each
slot's last L positions.  No host work keeps it.

The model's dict holds the published ``config.json`` keys this block reads
(:data:`MODEL_KEYS`).
"""
import numpy as np

MODEL_KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "num_attention_heads", "num_key_value_heads", "num_experts",
              "num_experts_per_tok", "num_dense_layers", "norm_eps",
              "norm_topk_prob", "routed_scaling_factor", "rope_theta",
              "conv_L_cache", "conv_bias", "layer_types")
CONV, ATTENTION = "conv", "full_attention"


def model_of(cfg: dict) -> dict:
    """The keys this block reads, out of a configuration that holds more."""
    out = {k: cfg[k] for k in MODEL_KEYS if k in cfg}
    if out.get("conv_bias"):
        raise ValueError("lfm2_moe: a convolution with a bias is not built")
    return out


def head_dim(model: dict) -> int:
    return model["hidden_size"] // model["num_attention_heads"]


def layer_kinds(model: dict, num_layers: int):
    """``layer_types`` of the first ``num_layers`` layers."""
    kinds = list(model["layer_types"][:num_layers])
    if len(kinds) != num_layers or set(kinds) - {CONV, ATTENTION}:
        raise ValueError("layer_types %r do not describe %d conv / "
                         "full_attention layers" % (kinds, num_layers))
    return kinds


def is_expert_layer(model: dict, i: int) -> bool:
    return i >= model["num_dense_layers"]


def param_shapes(model: dict, num_layers: int, vocab_size: int) -> dict:
    """Name -> shape of every parameter the step consumes.  ``*_gamma`` are
    float32, the rest the serving dtype.  The head is the embedding's
    transpose (tied): no matrix of its own."""
    d, H = model["hidden_size"], model["num_attention_heads"]
    Hkv, hd = model["num_key_value_heads"], head_dim(model)
    F, f = model["intermediate_size"], model["moe_intermediate_size"]
    E, L = model["num_experts"], model["conv_L_cache"]
    shapes = {"tok_embed_weight": (vocab_size, d)}
    for i, kind in enumerate(layer_kinds(model, num_layers)):
        p = "l%d_" % i
        shapes.update({p + "ln1_gamma": (d,), p + "ln2_gamma": (d,)})
        if kind == CONV:
            shapes.update({p + "in_proj_weight": (3 * d, d),
                           p + "conv_weight": (d, L),
                           p + "out_proj_weight": (d, d)})
        else:
            shapes.update({p + "q_weight": (H * hd, d),
                           p + "k_weight": (Hkv * hd, d),
                           p + "v_weight": (Hkv * hd, d),
                           p + "o_weight": (d, H * hd),
                           p + "q_norm_gamma": (hd,),
                           p + "k_norm_gamma": (hd,)})
        if is_expert_layer(model, i):
            p += "moe_"
            shapes.update({p + "router_weight": (d, E),
                           p + "expert_w1": (E, d, f),
                           p + "expert_w3": (E, d, f),
                           p + "expert_w2": (E, f, d)})
        else:
            shapes.update({p + "ff1_weight": (F, d), p + "ff3_weight": (F, d),
                           p + "ff2_weight": (d, F)})
    shapes["ln_f_gamma"] = (d,)
    return shapes


def is_float32_param(name: str) -> bool:
    return name.endswith(("_gamma", "_expert_bias"))


def state_shape(model: dict, num_layers: int, slots: int) -> tuple:
    """``(n_conv, S, L, d)``: the convolution state of every slot."""
    kinds = layer_kinds(model, num_layers)
    return (kinds.count(CONV), int(slots), model["conv_L_cache"],
            model["hidden_size"])


def attention_layers(model: dict, num_layers: int) -> int:
    return layer_kinds(model, num_layers).count(ATTENTION)


def rope_inv_freq(model: dict) -> np.ndarray:
    """(hd / 2,) float32: pair j turns by ``position * theta^(-2j / hd)``."""
    hd = head_dim(model)
    return (float(model["rope_theta"])
            ** (-np.arange(0, hd, 2, dtype=np.float64) / hd)
            ).astype(np.float32)


def segmented_conv(v, state, taps, positions, row_slot):
    """The short convolution of one step's rows with the slots' carried
    state (the module docstring has the rule).  ``v`` (R, d) in the state's
    dtype; ``state`` (S, L, d); ``taps`` (d, L); ``positions`` (R,), -1 a
    dead row; ``row_slot`` (R,).  Returns ``(z (R, d) float32, state)``."""
    import jax.numpy as jnp
    R = v.shape[0]
    S, L, _ = state.shape
    rows = jnp.arange(R)
    live = positions >= 0
    taps = taps.astype(jnp.float32)
    z = v.astype(jnp.float32) * taps[:, L - 1]
    for lag in range(1, L):
        src = jnp.maximum(rows - lag, 0)
        in_step = ((rows >= lag) & (row_slot[src] == row_slot)
                   & (positions[src] == positions - lag))
        carried = state[row_slot, (positions - lag) % L]
        prev = jnp.where(in_step[:, None], v[src], carried)
        prev = jnp.where((positions >= lag)[:, None], prev, 0)
        z = z + prev.astype(jnp.float32) * taps[:, L - 1 - lag]
    ahead = jnp.minimum(rows + L, R - 1)
    superseded = ((rows + L < R) & (row_slot[ahead] == row_slot)
                  & (positions[ahead] == positions + L))
    to = jnp.where(live & ~superseded, row_slot, S)     # S: dropped
    state = state.at[to, positions % L].set(v.astype(state.dtype),
                                            mode="drop")
    return z, state


class Decoder:
    """The step's mathematics for one geometry: ``slots`` decode rows and
    ``chunk_rows`` prefill rows a step, ``dtype`` parameters, pool and
    convolution state."""

    def __init__(self, model: dict, *, num_layers: int, vocab_size: int,
                 slots: int, chunk_rows: int, dtype):
        import jax.numpy as jnp
        from .sarvam_mla import serving_row_buckets
        self.model = model = model_of(model)
        self.num_layers, self.vocab_size = int(num_layers), int(vocab_size)
        self.slots, self.chunk_rows = int(slots), int(chunk_rows)
        self.dtype = jnp.dtype(dtype)
        self.kinds = layer_kinds(model, self.num_layers)
        self.hd = head_dim(model)
        self.inv_freq = rope_inv_freq(model)
        self.buckets = serving_row_buckets(
            self.slots + self.chunk_rows,
            dict(model, router_width=model["num_experts"]))

    # -- pieces -------------------------------------------------------------
    def _rms(self, x, gain):
        import jax
        import jax.numpy as jnp
        x32 = x.astype(jnp.float32)
        inv = jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True)
                            + self.model["norm_eps"])
        return (x32 * inv * gain).astype(self.dtype)

    def _rope(self, x, positions):
        """``x`` (R, heads, hd) turned by its row's position, in float32."""
        import jax.numpy as jnp
        ang = positions.astype(jnp.float32)[:, None, None] * self.inv_freq
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

    def _conv(self, p, pfx, h, state, rows):
        import jax
        import jax.numpy as jnp
        scope = jax.named_scope
        d = self.model["hidden_size"]
        with scope("in_proj"):
            bcx = h @ p[pfx + "in_proj_weight"].T                   # (R, 3d)
            b, c, x = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
            v = (b.astype(jnp.float32) * x.astype(jnp.float32)) \
                .astype(state.dtype)
        with scope("shift"):
            z, state = segmented_conv(v, state, p[pfx + "conv_weight"],
                                      rows["positions"], rows["slot"])
        with scope("out_proj"):
            y = (c.astype(jnp.float32) * z).astype(self.dtype)
            return y @ p[pfx + "out_proj_weight"].T, state

    def _attention(self, p, pfx, a, h, pool, rows, use_pallas):
        import jax
        import jax.numpy as jnp
        from ..ops import pallas_kernels as pk
        m = self.model
        H, Hkv, hd = m["num_attention_heads"], m["num_key_value_heads"], \
            self.hd
        R, S = h.shape[0], self.slots
        scope = jax.named_scope
        with scope("mx.decode.qkv"):
            q = (h @ p[pfx + "q_weight"].T).reshape(R, H, hd)
            k = (h @ p[pfx + "k_weight"].T).reshape(R, Hkv, hd)
            v = (h @ p[pfx + "v_weight"].T).reshape(R, Hkv, hd)
            q = self._rope(self._rms(q, p[pfx + "q_norm_gamma"]), rows["pos"])
            k = self._rope(self._rms(k, p[pfx + "k_norm_gamma"]), rows["pos"])
        # the pool is touched by these two and by nothing else
        with scope("mx.decode.kv_write"):
            pool = pk.kv_write(pool, a, k, v, rows["phys"], rows["off"],
                               use_pallas=use_pallas)
        with scope("mx.decode.attn"):
            scale = hd ** -0.5
            if use_pallas:
                o = jnp.concatenate([
                    pk.decode_attention_pool(q[:S], pool, a,
                                             rows["page_table"],
                                             rows["limit"][:S], scale),
                    pk.chunk_attention(q[S:], pool, a, rows["page_table"],
                                       rows["slot"][S:], rows["limit"][S:],
                                       scale, use_pallas=True)])
            else:
                o = pk.chunk_attention(q, pool, a, rows["page_table"],
                                       rows["slot"], rows["limit"], scale,
                                       use_pallas=False)
        with scope("mx.decode.proj"):
            return o.reshape(R, H * hd) @ p[pfx + "o_weight"].T, pool

    def _experts(self, p, pfx, h, live):
        import jax.numpy as jnp
        from ..parallel.moe import moe_ffn_held
        m = self.model
        E = m["num_experts"]
        bias = p.get(pfx + "expert_bias")
        if bias is None:
            bias = jnp.zeros((E,), jnp.float32)
        out, load = moe_ffn_held(
            h, p[pfx + "router_weight"], bias, None,
            tuple(p[pfx + "expert_" + w] for w in ("w1", "w3", "w2")),
            num_experts=E, first_expert=0, top_k=m["num_experts_per_tok"],
            route_norm=bool(m["norm_topk_prob"]),
            route_scale=m["routed_scaling_factor"], buckets=self.buckets,
            live=live)
        return out, jnp.stack([jnp.sum(load), jnp.sum(load > 0)]) \
            .astype(jnp.int32)

    # -- the step -------------------------------------------------------------
    def step(self, p, state, tokens, positions, seq_lens, phys, off,
             page_table, prev_tok, row_slot, out_row, use_pallas=None):
        """One step over the fixed budget of rows (the arguments as
        ``sarvam_mla.Decoder.step`` takes them; ``state`` the dict the
        module docstring describes).  Returns ``(next_tokens (S,), logits
        (S, V) float32, state, [picks, experts touched] summed over the
        expert layers)``."""
        import jax
        import jax.numpy as jnp
        from ..ops import pallas_kernels as pk
        if use_pallas is None:
            use_pallas = pk.pool_ops_are_pallas()
        S = self.slots
        scope = jax.named_scope
        live = positions >= 0
        limit = jnp.where(live, positions + 1, 0).astype(jnp.int32)
        limit = limit.at[:S].set(jnp.where(live[:S], seq_lens, 0))
        rows = {"pos": jnp.maximum(positions, 0), "positions": positions,
                "phys": phys, "off": off, "slot": row_slot, "limit": limit,
                "page_table": page_table}
        pool, conv = state["kv"], state["conv"]
        with scope("mx.decode.embed"):
            tokens = jnp.where(tokens < 0, prev_tok[row_slot], tokens)
            x = p["tok_embed_weight"][tokens]
        counts = jnp.zeros((2,), jnp.int32)
        n_conv = n_attn = 0
        for i, kind in enumerate(self.kinds):
            pfx = "l%d_" % i
            with scope("mx.decode.norm"):
                h = self._rms(x, p[pfx + "ln1_gamma"])
            if kind == CONV:
                with scope("mx.decode.conv"):
                    y, layer_state = self._conv(p, pfx, h, conv[n_conv], rows)
                    conv = conv.at[n_conv].set(layer_state)
                n_conv += 1
            else:
                y, pool = self._attention(p, pfx, n_attn, h, pool, rows,
                                          use_pallas)
                n_attn += 1
            x = x + y
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
            logits = jnp.dot(last, p["tok_embed_weight"].T,
                             preferred_element_type=jnp.float32)
        with scope("mx.decode.sample"):
            next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return next_tok, logits, {"kv": pool, "conv": conv}, counts
