"""The parameter tree of this family, as the program's checkpoint format
has it: ``embed``, ``final_norm`` and one node ``layers`` whose leaves
are stacked over the layers, ``x @ W`` orientation; a mixture's experts
are stacked behind the layers and its router is float32.  The output
head is the embedding.
"""

from __future__ import annotations


def weight_specs(model: dict) -> list:
    d, n = model["hidden_size"], model["num_hidden_layers"]
    h, kv, hd = (model["num_attention_heads"],
                 model["num_key_value_heads"], model["head_dim"])
    f, v = model["intermediate_size"], model["vocab_size"]
    e = model.get("num_local_experts", 0)
    specs = [
        (("embed",), (v, d), "normal", d ** -0.5, "served"),
        (("layers", "attn_norm"), (n, d), "around_one", 0.1, "served"),
        (("layers", "wq"), (n, d, h * hd), "normal", d ** -0.5, "served"),
        (("layers", "wk"), (n, d, kv * hd), "normal", d ** -0.5, "served"),
        (("layers", "wv"), (n, d, kv * hd), "normal", d ** -0.5, "served"),
        (("layers", "wo"), (n, h * hd, d), "normal", (h * hd) ** -0.5,
         "served"),
        (("layers", "mlp_norm"), (n, d), "around_one", 0.1, "served"),
        (("final_norm",), (d,), "around_one", 0.1, "served"),
    ]
    lead = (n, e) if e else (n,)
    if e:
        specs.append(
            (("layers", "router"), (n, d, e), "normal", d ** -0.5, "float32")
        )
    specs += [
        (("layers", "w_gate"), lead + (d, f), "normal", d ** -0.5, "served"),
        (("layers", "w_up"), lead + (d, f), "normal", d ** -0.5, "served"),
        (("layers", "w_down"), lead + (f, d), "normal", f ** -0.5, "served"),
    ]
    return specs
