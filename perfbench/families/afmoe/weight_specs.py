"""The parameter tree of this family, as the program's checkpoint format
has it: ``embed``, the untied ``lm_head [hidden, vocab]``,
``final_norm`` and, under ``layers``, one stack a KIND of layer part,
each leaf stacked over the layers that have the part: ``attention``
(the full layers) and ``sliding`` (the window layers) operators, with
the same leaves; ``dense`` and ``moe`` feed-forwards; ``x @ W``
orientation.  A layer reads the index of its part's stack that the
layers before it leave.  ``wg`` is the attention's output gate, as wide
as ``wq``; ``attn_post_norm`` and ``mlp_post_norm`` the second norm of
each block; ``shared_gate`` / ``shared_up`` / ``shared_down`` the one
shared expert.  The router and ``expert_bias`` are float32.
"""

from __future__ import annotations


def weight_specs(model: dict) -> list:
    d, v = model["hidden_size"], model["vocab_size"]
    h, kv = model["num_attention_heads"], model["num_key_value_heads"]
    hd = model["head_dim"]
    f, fm = model["intermediate_size"], model["moe_intermediate_size"]
    e = model["num_experts"]
    types = model["layer_types"]
    counts = {
        "attention": sum(t == "full_attention" for t in types),
        "sliding": sum(t == "sliding_attention" for t in types),
    }
    nd = model["num_dense_layers"]
    nm = model["num_hidden_layers"] - nd
    one, served = ("around_one", 0.1, "served"), "served"
    specs = [
        (("embed",), (v, d), "normal", d ** -0.5, served),
        (("lm_head",), (d, v), "normal", d ** -0.5, served),
        (("final_norm",), (d,)) + one,
    ]
    for part, n in counts.items():
        at = ("layers", part)
        specs += [
            (at + ("attn_norm",), (n, d)) + one,
            (at + ("wq",), (n, d, h * hd), "normal", d ** -0.5, served),
            (at + ("wk",), (n, d, kv * hd), "normal", d ** -0.5, served),
            (at + ("wv",), (n, d, kv * hd), "normal", d ** -0.5, served),
            (at + ("wo",), (n, h * hd, d), "normal", (h * hd) ** -0.5,
             served),
            (at + ("q_norm",), (n, hd)) + one,
            (at + ("k_norm",), (n, hd)) + one,
            (at + ("wg",), (n, d, h * hd), "normal", d ** -0.5, served),
            (at + ("attn_post_norm",), (n, d)) + one,
        ]
    specs += [
        (("layers", "dense", "mlp_norm"), (nd, d)) + one,
        (("layers", "dense", "w_gate"), (nd, d, f), "normal", d ** -0.5,
         served),
        (("layers", "dense", "w_up"), (nd, d, f), "normal", d ** -0.5,
         served),
        (("layers", "dense", "w_down"), (nd, f, d), "normal", f ** -0.5,
         served),
        (("layers", "dense", "mlp_post_norm"), (nd, d)) + one,
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
        (("layers", "moe", "shared_gate"), (nm, d, fm), "normal", d ** -0.5,
         served),
        (("layers", "moe", "shared_up"), (nm, d, fm), "normal", d ** -0.5,
         served),
        (("layers", "moe", "shared_down"), (nm, fm, d), "normal", fm ** -0.5,
         served),
        (("layers", "moe", "mlp_post_norm"), (nm, d)) + one,
    ]
    return specs
