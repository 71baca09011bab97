"""A served model's sizes as the configuration file states them, under the
published (Hugging Face ``config.json``) key names, and the check that the
program serves exactly those sizes. A file's ``config`` holds the published
values unchanged; ``served_as`` names where the served architecture departs
from them."""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Model:
    name: str            # the program's registry name
    d: int               # hidden_size
    layers: int          # num_hidden_layers
    heads: int           # num_attention_heads
    kv_heads: int        # num_key_value_heads
    head_dim: int
    ff: int              # intermediate_size
    vocab: int
    eps: float           # rms_norm_eps
    theta: float         # rope_theta
    qk_norm: bool
    tied: bool           # tie_word_embeddings
    dtype: str           # served parameter and activation type
    rotary: float = 1.0  # partial_rotary_factor
    qkv_bias: bool = False   # use_qkv_bias / attention_bias

    @classmethod
    def from_conf(cls, group: dict, dtype: str) -> "Model":
        """The model as served: the published ``config``, with the keys of
        ``served_as`` (where the program departs from the published
        architecture) in their place."""
        c = {**group["config"], **group.get("served_as", {})}
        return cls(name=group["model"], d=c["hidden_size"],
                   layers=c["num_hidden_layers"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"],
                   head_dim=c.get("head_dim", c["hidden_size"]
                                  // c["num_attention_heads"]),
                   ff=c["intermediate_size"], vocab=c["vocab_size"],
                   eps=c["rms_norm_eps"], theta=c["rope_theta"],
                   qk_norm=group.get("qk_norm", False),
                   tied=c["tie_word_embeddings"], dtype=dtype,
                   rotary=c.get("partial_rotary_factor", 1.0),
                   qkv_bias=c.get("use_qkv_bias",
                                  c.get("attention_bias", False)))

    def mismatches(self, cfg) -> list:
        """Fields in which the program's ``ModelConfig`` differs from this
        file; empty when the program serves the stated model."""
        pairs = {"d_model": self.d, "num_layers": self.layers,
                 "num_heads": self.heads, "num_kv_heads": self.kv_heads,
                 "head_dim": self.head_dim, "d_ff": self.ff,
                 "vocab_size": self.vocab, "norm_eps": self.eps,
                 "rope_theta": self.theta, "qk_norm": self.qk_norm,
                 "tie_embeddings": self.tied,
                 "param_dtype": self.dtype, "activation_dtype": self.dtype,
                 "attn_type": "gqa", "layer_pattern": ("global",),
                 "mlp_act": "swiglu", "use_rope": True, "moe": None,
                 "attn_logit_softcap": None, "final_logit_softcap": None,
                 "local_window": None, "prefix_layers": 0}
        bad = [f"{k}: program {getattr(cfg, k)!r} != file {v!r}"
               for k, v in pairs.items() if getattr(cfg, k) != v]
        # the program's block rotates the whole head and has no q/k/v bias
        if self.rotary != 1.0:
            bad.append(f"partial_rotary_factor: program 1.0 != file "
                       f"{self.rotary!r}")
        if self.qkv_bias:
            bad.append("use_qkv_bias: program False != file True")
        return bad

    # -- parameter counts (for the work counts) -------------------------
    @property
    def layer_matmul_params(self) -> int:
        d, h, kv, dh, f = (self.d, self.heads, self.kv_heads, self.head_dim,
                           self.ff)
        return d * h * dh * 2 + d * kv * dh * 2 + 3 * d * f
