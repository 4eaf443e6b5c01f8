"""Operations and bytes of the hybrid Mamba-2 / attention decoder's work
(``configs/hybrid_decoder.py``), from its shapes, by the rules of
``flops.py``: lower bounds of what the algorithm has to do, recomputation
never counted.

* a Mamba-2 mixer costs its two projections and its depthwise conv, and
  the chunked SSD at the published chunk size: per chunk, ``C B^T`` once a
  group over the chunk's (query, key) pairs, then for each head its decayed
  product with ``X``, the carried state read through ``C`` and the state's
  update;
* an attention layer costs its projections and causal attention;
* every layer's MLP is a TT layer at its least cost (``flops.py``);
* training is forward plus backward: 3x for all but the TT layers, which
  count their forward, input-gradient and core-gradient networks.
"""

from __future__ import annotations

from .flops import attention_pairs, tt_forward_flops, tt_train_flops


def sizes(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return dict(types=cfg["layer_types"], d=d, H=h,
                KV=cfg["num_key_value_heads"],
                hd=cfg.get("head_dim", d // h), F=cfg["intermediate_size"],
                V=cfg["vocab_size"], mh=cfg["mamba_n_heads"],
                mp=cfg["mamba_d_head"], N=cfg["mamba_d_state"],
                G=cfg["mamba_n_groups"], W=cfg["mamba_d_conv"],
                chunk=cfg["mamba_chunk_size"], rank=cfg["tnn"]["rank"],
                k=cfg["tnn"]["num_factors"])


def ssd_flops(groups: int, heads_per_group: int, seq: int, state: int,
              head_dim: int, chunk: int) -> int:
    """Forward FLOPs of the chunked SSD over ``groups`` sequences-and-
    groups of ``seq`` tokens, each shared by ``heads_per_group`` heads."""
    chunk = min(chunk, seq)
    pairs = seq // chunk * attention_pairs(0, chunk)
    per_head = 2 * pairs * head_dim + 4 * seq * state * head_dim
    return groups * (2 * pairs * state + heads_per_group * per_head)


def ssd_call(batch: int, heads: int, groups: int, seq: int, state: int,
             head_dim: int, chunk: int) -> tuple[int, int]:
    """(FLOPs, bytes) the chunked SSD forward over ``batch`` rows of
    ``seq`` tokens needs at least, whatever layout a kernel reads: per
    head, ``X`` read and ``Y`` written in bf16, ``dt`` (or the log-decay it
    gives) as one f32 a token, and the final state in f32; ``B`` and ``C``
    read in bf16 once per group and row, not once per head."""
    flops = ssd_flops(batch * groups, heads // groups, seq, state, head_dim,
                      chunk)
    nbytes = (batch * heads * (seq * (2 * 2 * head_dim + 4)
                               + 4 * state * head_dim)
              + batch * groups * seq * 2 * 2 * state)
    return flops, nbytes


def forward_flops(cfg: dict, batch: int, seq: int) -> dict:
    """Forward FLOPs of a batch through every layer and the tied head, by
    part (the MLPs apart: see :func:`train_step_flops`)."""
    s = sizes(cfg)
    tokens = batch * seq
    d, H, KV, hd = s["d"], s["H"], s["KV"], s["hd"]
    DI = s["mh"] * s["mp"]
    conv = DI + 2 * s["G"] * s["N"]
    n_attn = s["types"].count("attention")
    n_mamba = s["types"].count("mamba")
    mamba = (2 * tokens * d * (2 * DI + 2 * s["G"] * s["N"] + s["mh"])
             + 2 * tokens * DI * d + 2 * tokens * s["W"] * conv
             + ssd_flops(batch * s["G"], s["mh"] // s["G"], seq, s["N"],
                         s["mp"], s["chunk"]))
    attn = (2 * tokens * (2 * d * H * hd + 2 * d * KV * hd)
            + 4 * hd * H * batch * attention_pairs(0, seq))
    return {"mamba": n_mamba * mamba, "attn": n_attn * attn,
            "head": 2 * tokens * d * s["V"]}


def train_step_flops(cfg: dict, batch: int, seq: int) -> int:
    """FLOPs one training step needs: forward and backward, no recompute."""
    s = sizes(cfg)
    tokens = batch * seq
    L = len(s["types"])
    mlp = L * (2 * tt_train_flops(s["F"], s["d"], s["rank"], s["k"], tokens)
               + tt_train_flops(s["d"], s["F"], s["rank"], s["k"], tokens))
    return 3 * sum(forward_flops(cfg, batch, seq).values()) + mlp


def mlp_forward_flops(cfg: dict, tokens: int) -> int:
    """Forward FLOPs of every layer's TT MLP."""
    s = sizes(cfg)
    return len(s["types"]) * (
        2 * tt_forward_flops(s["F"], s["d"], s["rank"], s["k"], tokens)
        + tt_forward_flops(s["d"], s["F"], s["rank"], s["k"], tokens))
