"""A small size of the LoRA cell for the CPU tests (beside ``tiny``,
whose seed it shares): the widths shrink through the workload's
``config`` entries."""
import chipbench_path  # noqa: F401
from tiny import SEED

TINY = {
    "deepseek-v2-lite.lora": {
        "m": 2, "n": 2, "seq_len": 16, "sample_positions": 8,
        "config": {"hidden_size": 64, "num_attention_heads": 4,
                   "kv_lora_rank": 32, "qk_nope_head_dim": 16,
                   "qk_rope_head_dim": 8, "v_head_dim": 16,
                   "intermediate_size": 96, "moe_intermediate_size": 32,
                   "n_routed_experts": 4, "router_experts": 8,
                   "num_experts_per_tok": 3, "num_hidden_layers": 3,
                   "vocab_size": 128,
                   "lora": {"rank": 4, "alpha": 8,
                            "targets": ["wq", "wkva", "wkvb", "wo"]}}},
}


def run(name, seconds=0.5):
    """One run of the tiny cell in this process."""
    from chipbench import harness
    return harness.run_cell(name, SEED, seconds, False, allow_cpu=True,
                            overrides=TINY[name])
