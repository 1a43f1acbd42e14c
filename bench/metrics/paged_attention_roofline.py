"""paged_attention_roofline: the least time the chip could take for the
paged-attention kernel calls of the traced window over their device time,
in percent.

Least time: the KV bytes the window's decode steps must read, at HBM
bandwidth. Every output token streamed in the window after a request's
first (that one comes from the prime) was decoded over its full causal
context, so token i of a request with a P-token prompt read P + i keys and
values in every layer: (P + i) x num_hidden_layers x 2 (K and V) x
num_key_value_heads x head_dim x 2 bytes (the bf16 KV cache). That is the
token walk `serve_mfu` makes. A change of the KV cache's precision (ROADMAP
S8) changes what a kernel must read, and the count has to be revisited with
it.

Kernel time: the device time of the Mosaic custom calls the program names
`paged_attention` (its `pallas_call(name=...)`, which names the HLO
instruction `%paged_attention.<n>`), not a match on operand shapes, so a
rewrite of the kernel's operands keeps the metric; and not the
`paged_attention.attention` scope, which also holds the reshapes around the
kernel (and which the device trace's labels do not carry). Any correct
kernel reads at least the counted bytes, so the share stays at or under
100%."""
from __future__ import annotations

KV_BYTES = 2                      # bf16 K and V
# the trace labels a device op with its HLO text, whose instruction name
# the program's kernel name gives
KERNEL = r"^%paged_attention(?:\.\d+)? = \S+ custom-call\("


def read(ctx):
    tr = ctx.get("trace")
    if ctx.get("kind") != "serve" or tr is None:
        return None
    kernel_s = tr.op_seconds(KERNEL)
    if not kernel_s:
        return None
    c = ctx["config"]["config"]
    hd = c.get("head_dim", c["hidden_size"] // c["num_attention_heads"])
    per_key = c["num_hidden_layers"] * 2 * c["num_key_value_heads"] * hd \
        * KV_BYTES
    w0, w1 = ctx["w0"], ctx["w1"]
    keys = 0
    for r, p in zip(ctx["results"], ctx["planned"]):
        P = len(p.prompt)
        keys += sum(P + i for i, t in enumerate(r["stamps"])
                    if i >= 1 and w0 <= t < w1)
    least = keys * per_key / (ctx["chips"] * ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s
