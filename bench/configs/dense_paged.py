"""How the system under test serves a dense GQA configuration: the
program's ``ArchConfig`` built from the configuration file's own numbers
(so that no edit to the program's registry moves a cell) and the runtime
options of its paged deployment. A configuration file names this module
under ``"program"``; a configuration of another architecture names a
module of its own beside it, and ``run.py`` stays as it is.

Unlike the plain reference beside it (``dense_gqa.py``), this module uses
the program's own types: it describes the system, it does not check it.
"""

from __future__ import annotations


def arch_config(cfg: dict):
    from repro.configs.base import ArchConfig, AttnSpec, LayerSpec, MLPSpec

    d = cfg["hidden_size"]
    attn = AttnSpec(num_heads=cfg["num_attention_heads"],
                    num_kv_heads=cfg["num_key_value_heads"],
                    head_dim=cfg.get("head_dim") or d // cfg[
                        "num_attention_heads"],
                    sliding_window=cfg.get("sliding_window"))
    mlp = MLPSpec(d_ff=cfg["intermediate_size"], activation="silu",
                  gated=True)
    return ArchConfig(name=cfg["name"], arch_type="dense", d_model=d,
                      vocab_size=cfg["vocab_size"],
                      pattern=(LayerSpec(attn, mlp),),
                      num_blocks=cfg["num_hidden_layers"],
                      rope_theta=float(cfg["rope_theta"]),
                      tie_embeddings=bool(cfg["tie_word_embeddings"]),
                      norm_eps=float(cfg["rms_norm_eps"]),
                      source=cfg["source"])


def runtime_opts(cfg: dict):
    """bf16 weights over the paged pool's only layout: int8 key and value
    codes with one float32 scale per token and kv head."""
    from repro.models.transformer import RuntimeOpts

    return RuntimeOpts(remat=False, quantized_kv=True,
                       moe_capacity_factor=0.0)
