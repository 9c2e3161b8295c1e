"""The parameter tree of this family, as the program's checkpoint format
has it: ``embed`` (also the output head), ``final_norm`` and, under
``layers``, one stack a KIND of layer part, each leaf stacked over the
layers that have the part: ``attention`` and ``conv`` operators,
``dense`` and ``moe`` feed-forwards; ``x @ W`` orientation.  A layer
reads the index of its part's stack that the layers before it leave.
``conv_in`` holds the three gates side by side (``[B, C, X]``);
``conv_w`` is the depthwise kernel ``[hidden, conv_L_cache]``, its last
tap the newest input.  The router and ``expert_bias`` are float32.
"""

from __future__ import annotations


def weight_specs(model: dict) -> list:
    d, v = model["hidden_size"], model["vocab_size"]
    h, kv = model["num_attention_heads"], model["num_key_value_heads"]
    hd = d // h
    f, fm = model["intermediate_size"], model["moe_intermediate_size"]
    e, taps = model["num_experts"], model["conv_L_cache"]
    types = model["layer_types"]
    na = sum(t == "full_attention" for t in types)
    nc = sum(t == "conv" for t in types)
    nd = model["num_dense_layers"]
    nm = model["num_hidden_layers"] - nd
    one, served = ("around_one", 0.1, "served"), "served"
    specs = [
        (("embed",), (v, d), "normal", d ** -0.5, served),
        (("final_norm",), (d,)) + one,
        (("layers", "attention", "attn_norm"), (na, d)) + one,
        (("layers", "attention", "wq"), (na, d, h * hd), "normal",
         d ** -0.5, served),
        (("layers", "attention", "wk"), (na, d, kv * hd), "normal",
         d ** -0.5, served),
        (("layers", "attention", "wv"), (na, d, kv * hd), "normal",
         d ** -0.5, served),
        (("layers", "attention", "wo"), (na, h * hd, d), "normal",
         (h * hd) ** -0.5, served),
        (("layers", "attention", "q_norm"), (na, hd)) + one,
        (("layers", "attention", "k_norm"), (na, hd)) + one,
        (("layers", "conv", "conv_norm"), (nc, d)) + one,
        (("layers", "conv", "conv_in"), (nc, d, 3 * d), "normal",
         d ** -0.5, served),
        (("layers", "conv", "conv_w"), (nc, d, taps), "normal",
         taps ** -0.5, served),
        (("layers", "conv", "conv_out"), (nc, d, d), "normal", d ** -0.5,
         served),
        (("layers", "dense", "mlp_norm"), (nd, d)) + one,
        (("layers", "dense", "w_gate"), (nd, d, f), "normal", d ** -0.5,
         served),
        (("layers", "dense", "w_up"), (nd, d, f), "normal", d ** -0.5,
         served),
        (("layers", "dense", "w_down"), (nd, f, d), "normal", f ** -0.5,
         served),
        (("layers", "moe", "mlp_norm"), (nm, d)) + one,
        (("layers", "moe", "router"), (nm, d, e), "normal", d ** -0.5,
         "float32"),
        (("layers", "moe", "expert_bias"), (nm, e), "normal", 0.01,
         "float32"),
        (("layers", "moe", "w_gate"), (nm, e, d, fm), "normal", d ** -0.5,
         served),
        (("layers", "moe", "w_up"), (nm, e, d, fm), "normal", d ** -0.5,
         served),
        (("layers", "moe", "w_down"), (nm, e, fm, d), "normal", fm ** -0.5,
         served),
    ]
    return specs
