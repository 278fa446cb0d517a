"""Operations of the LoRA fine-tuning step of an MLA + MoE decoder,
counted from the configuration's shapes (``configs/<name>.json``): the
work the job must do, which ``lora_mfu`` holds against the chip's peak.

Per token: the forward of the chip's share (every matrix product,
attention's two products over the causal half of the keys, the routed
experts at the pairs this chip computed), the backward of the
activations (each product's input gradient, but not where the input is
the frozen embedding; attention's products both ways), and the
adapters' weight gradients. Layers recomputed in the backward pass are
not counted again. Elementwise work, norms, softmax and the loss are
left out."""
from __future__ import annotations


def projections(cfg: dict) -> dict:
    """(in, out) of each MLA projection."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    r = cfg["kv_lora_rank"]
    return {"wq": (d, h * (nope + rope)), "wkva": (d, r + rope),
            "wkvb": (r, h * (nope + dv)), "wo": (h * dv, d)}


def step_flops(cfg: dict, tokens: int, seq_len: int, held_pairs) -> float:
    """FLOPs of one local step over ``tokens`` tokens of sequences of
    ``seq_len``; ``held_pairs``: the (token, choice) pairs computed by
    the held experts in each MoE layer of that step."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h = cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv, rank = cfg["v_head_dim"], cfg["lora"]["rank"]
    f, fs = cfg["moe_intermediate_size"], (cfg["moe_intermediate_size"]
                                           * cfg["n_shared_experts"])
    k = cfg["first_k_dense_replace"]
    layers = cfg["num_hidden_layers"]
    proj = projections(cfg)
    keys = (seq_len + 1) / 2                      # causal: keys per query
    macs_fwd = macs_bwd = 0.0
    for i in range(layers):
        for name, (din, dout) in proj.items():
            base = din * dout
            lora = (din + dout) * rank
            macs_fwd += base + lora
            first = i == 0 and name in ("wq", "wkva")
            macs_bwd += (0 if first else base + lora) + lora  # dx; dA, dB
        att = h * keys * (qk + dv)
        macs_fwd += att
        macs_bwd += 2 * att
        if i < k:
            ffn = 3 * d * cfg["intermediate_size"]
        else:
            ffn = d * cfg["router_experts"] + 3 * d * fs
        macs_fwd += ffn
        macs_bwd += ffn
    macs_fwd += d * v
    macs_bwd += d * v
    total = 2 * tokens * (macs_fwd + macs_bwd)
    expert = 3 * d * f                             # one (token, expert) pair
    total += 2 * 2 * expert * float(sum(held_pairs))    # forward + dx
    return total
