"""Operations and bytes of a DeepSeek-V2-style model (MLA, a leading dense
layer, expert layers of routed and shared experts) at one chip's share of
an expert-parallel deployment, computed from the configuration's keys (the
published ``config.json``'s, ``n_routed_experts`` being the experts held
here and ``deployment.router_outputs`` the router's width).

A multiply-add counts as 2 FLOPs.  Routed experts count at their expected
load here: a token picks ``num_experts_per_tok`` of ``router_outputs``, so it
makes k * held / E passes through this chip's experts on average.  Padding,
masked attention slots and recomputation are not counted.
"""
from __future__ import annotations


def _widths(cfg: dict) -> dict:
    return {
        "d": cfg["hidden_size"], "h": cfg["num_attention_heads"], "lora": cfg["kv_lora_rank"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
        "v": cfg["v_head_dim"], "f": cfg["moe_intermediate_size"],
        "shared": cfg["n_shared_experts"], "held": cfg["n_routed_experts"],
        "e": cfg["deployment"]["router_outputs"], "k": cfg["num_experts_per_tok"],
    }


def expected_routed_passes(cfg: dict) -> float:
    """Passes through held experts per token: k * held / E (0.75 for 6 of 64
    with 8 held)."""
    w = _widths(cfg)
    return w["k"] * w["held"] / w["e"]


def expected_experts_hit(cfg: dict, n: int) -> float:
    """Held experts that n tokens are expected to reach: each held expert is
    missed by a token with probability 1 - k/E, by all n with (1 - k/E)^n."""
    w = _widths(cfg)
    return w["held"] * (1.0 - (1.0 - w["k"] / w["e"]) ** n)


def moe_pass_flops(cfg: dict, n: int) -> float:
    """One forward pass of n tokens through one model's expert layer here:
    the router, the shared experts and the expected routed passes."""
    w = _widths(cfg)
    router = 2 * n * w["d"] * w["e"]
    shared = 6 * n * w["d"] * w["shared"] * w["f"]
    routed = 6 * n * expected_routed_passes(cfg) * w["d"] * w["f"]
    return router + shared + routed


def moe_pass_bytes(cfg: dict, n: int, weight_bytes: int = 2) -> float:
    """Weight bytes that pass must read at least: the shared experts, the
    router (float32) and the held experts expected to be hit."""
    w = _widths(cfg)
    shared = 3 * w["d"] * w["shared"] * w["f"] * weight_bytes
    router = w["d"] * w["e"] * 4
    routed = expected_experts_hit(cfg, n) * 3 * w["d"] * w["f"] * weight_bytes
    return shared + router + routed


def moe_pass_roofline_s(cfg: dict, n: int, peak: dict) -> float:
    """The least time of that pass: max(FLOPs / bf16 peak, bytes / HBM)."""
    return max(moe_pass_flops(cfg, n) / peak["bf16_flops"],
               moe_pass_bytes(cfg, n) / peak["hbm_bytes_per_s"])


def fleet_call_moe_passes(cfg: dict, groups: int, batch: int, prompt_len: int,
                          gen_tokens: int) -> list[tuple[int, int]]:
    """(tokens per pass, number of passes) of one fleet call: every group's
    prefill of batch x prompt_len tokens and its gen_tokens - 1 decode steps
    of batch tokens, through each expert layer of its model."""
    layers = groups * (cfg["num_hidden_layers"] - cfg["first_k_dense_replace"])
    return [(batch * prompt_len, layers), (batch, layers * (gen_tokens - 1))]


def request_flops(cfg: dict, prompt_len: int, gen_tokens: int) -> float:
    """Forward FLOPs of one greedy request at this chip's share: the prompt
    and the ``gen_tokens - 1`` fed-back tokens pass every layer (MLA's
    projections and the expansion of each position's latent into per-head K
    and V, causal attention over the positions before it, the dense layer or
    the router, shared and expected routed experts); the unembedding over
    the vocabulary slice runs once per generated token."""
    w = _widths(cfg)
    d, h = w["d"], w["h"]
    positions = prompt_len + gen_tokens - 1
    mla = 2 * d * h * (w["nope"] + w["rope"]) + 2 * d * (w["lora"] + w["rope"])
    mla += 2 * w["lora"] * h * (w["nope"] + w["v"]) + 2 * h * w["v"] * d
    attended = positions * (positions + 1) // 2
    attn = 2 * h * (w["nope"] + w["rope"] + w["v"]) * attended
    dense_layers = cfg["first_k_dense_replace"]
    moe_layers = cfg["num_hidden_layers"] - dense_layers
    per_token = (cfg["num_hidden_layers"] * mla + dense_layers * 6 * d * cfg["intermediate_size"]
                 + moe_layers * moe_pass_flops(cfg, 1))
    unembed = 2 * d * cfg["vocab_size"] * gen_tokens
    return per_token * positions + cfg["num_hidden_layers"] * attn + unembed
