"""Olmo-Hybrid parameter list for one pipeline stage, by FSDP2 unit.

Shapes follow the published config.json. The layer pattern
(``layer_types``) mixes two block kinds:

- ``linear_attention``: a Gated DeltaNet token mixer as in FLA's
  ``GatedDeltaNet``, which the ``linear_*`` keys describe: q and k
  projections to ``linear_num_key_heads * linear_key_head_dim``, v and the
  output gate g to ``linear_num_value_heads * linear_value_head_dim``, the
  per-head decay input a and beta b, a depthwise causal conv of
  ``linear_conv_kernel_dim`` over the concatenated q, k and v channels,
  ``A_log`` and ``dt_bias`` per head, the gated RMSNorm over a value head
  (``o_norm``) and the output projection;
- ``full_attention``: Olmo-2/3 attention, q, k, v and o projections of
  ``num_attention_heads * head_dim`` (head_dim = hidden / heads) and the
  q and k RMSNorms over the whole projection.

Both blocks carry the SiLU-gated MLP and Olmo's two post-norms
(``post_attention_layernorm``, ``post_feedforward_layernorm``); no
projection has a bias. The first stage holds the token embedding, the last
the final norm and the untied LM head.

FSDP2 (``fully_shard`` per block, the stage's root module holding the
embedding) shards every parameter on dim 0, padded to a multiple of the
FSDP degree, and all-gathers a unit's shards as one flat buffer.
"""
from __future__ import annotations

import math


def _mlp(cfg):
    h, ff = cfg["hidden_size"], cfg["intermediate_size"]
    return [("mlp.gate_proj.weight", (ff, h)), ("mlp.up_proj.weight", (ff, h)),
            ("mlp.down_proj.weight", (h, ff))]


def block(cfg, kind: str):
    """[(name, shape)] of one decoder block of ``kind``, in
    module-registration order (torch ``Linear`` weights are (out, in))."""
    h = cfg["hidden_size"]
    if kind == "linear_attention":
        hk = cfg["linear_num_key_heads"]
        hv = cfg["linear_num_value_heads"]
        k = hk * cfg["linear_key_head_dim"]
        v = hv * cfg["linear_value_head_dim"]
        mix = [("linear_attn.q_proj.weight", (k, h)),
               ("linear_attn.k_proj.weight", (k, h)),
               ("linear_attn.v_proj.weight", (v, h)),
               ("linear_attn.g_proj.weight", (v, h)),
               ("linear_attn.a_proj.weight", (hv, h)),
               ("linear_attn.b_proj.weight", (hv, h)),
               ("linear_attn.conv1d.weight",
                (2 * k + v, 1, cfg["linear_conv_kernel_dim"])),
               ("linear_attn.A_log", (hv,)),
               ("linear_attn.dt_bias", (hv,)),
               ("linear_attn.o_norm.weight",
                (cfg["linear_value_head_dim"],)),
               ("linear_attn.o_proj.weight", (h, v))]
    elif kind == "full_attention":
        q = cfg["num_attention_heads"] * (h // cfg["num_attention_heads"])
        kv = cfg["num_key_value_heads"] * (h // cfg["num_attention_heads"])
        mix = [("self_attn.q_proj.weight", (q, h)),
               ("self_attn.k_proj.weight", (kv, h)),
               ("self_attn.v_proj.weight", (kv, h)),
               ("self_attn.o_proj.weight", (h, q)),
               ("self_attn.q_norm.weight", (q,)),
               ("self_attn.k_norm.weight", (kv,))]
    else:
        raise ValueError(f"unknown layer type {kind!r}")
    return mix + _mlp(cfg) + [("post_attention_layernorm.weight", (h,)),
                              ("post_feedforward_layernorm.weight", (h,))]


def stage_units(cfg):
    """[(unit, [(name, shape)])] of the configuration's pipeline stage, in
    forward order: the root unit (the embedding on the first stage), then
    one unit per block; the last stage's final norm and LM head join the
    root unit, which FSDP2 gathers first."""
    dep = cfg["deployment"]
    h = cfg["hidden_size"]
    root = []
    if dep["stage"] == 0:
        root.append(("model.embed_tokens.weight", (cfg["vocab_size"], h)))
    if dep["stage"] == dep["pipeline_stages"] - 1:
        root += [("model.norm.weight", (h,)),
                 ("lm_head.weight", (cfg["vocab_size"], h))]
    first = dep["first_layer"]
    units = [("root", root)] if root else []
    for i, kind in enumerate(cfg["layer_types"]):
        units.append((f"model.layers.{first + i}", [
            (f"model.layers.{first + i}.{name}", shape)
            for name, shape in block(cfg, kind)]))
    return units


def elements(shape, degree: int = 1) -> int:
    """Elements of a parameter with dim 0 padded to a multiple of
    ``degree`` (FSDP2's dim-0 sharding)."""
    return -(-shape[0] // degree) * degree * math.prod(shape[1:])


def unit_sizes(cfg):
    """[(unit, elements)] of the stage's FSDP2 units in forward order, each
    parameter padded for the deployment's FSDP degree; a rank's shard of a
    unit is 1/degree of it."""
    degree = int(cfg["deployment"]["fsdp_parallel"])
    return [(unit, sum(elements(s, degree) for _, s in params))
            for unit, params in stage_units(cfg)]


def model_parameters(cfg, layer_types) -> int:
    """Parameters of the whole model with ``layer_types``: the embedding,
    the blocks, the final norm and the untied LM head."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    blocks = sum(elements(s) for kind in layer_types
                 for _, s in block(cfg, kind))
    return blocks + 2 * v * h + h


def gradient_ready_order(cfg):
    """[(name, elements)] of the stage's parameters in the order their
    gradients become ready in the backward pass: last block first, inside
    a block the reverse of registration, the root unit last."""
    return [(name, elements(shape))
            for _, params in reversed(stage_units(cfg))
            for name, shape in reversed(params)]
