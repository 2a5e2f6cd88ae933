"""``attention_paged``'s share of its roofline over the traced job: the
least time the chip could take for the work the live context needs (KV
bytes of the pages in use each step, queries and outputs; the score and
value FLOPs over those tokens), over the kernel's device time in the
trace. The kernel is found by the name the trace prints for it."""
from bench import work

LAYER = "kernel attention_paged"
MOVES = "out_tok_s"
KERNEL_NAMES = ("attention_paged",)


def is_kernel(name: str) -> bool:
    return any(k in name for k in KERNEL_NAMES)


def read(ctx):
    t = ctx.device.op_seconds(is_kernel)
    if t <= 0 or not ctx.rounds:
        return None
    ops = nbytes = 0
    for r in ctx.rounds:
        o, b = work.attention_paged_work(
            ctx.model, kv_tokens=int(r["pool_used"]) * ctx.page_tokens,
            queries=int(r["active"]), n_bits=ctx.kv_bits)
        ops += ctx.sync_every * o
        nbytes += ctx.sync_every * b
    least = max(ops / ctx.peaks["bf16_flops_s"],
                nbytes / ctx.peaks["hbm_bytes_s"])
    return 100.0 * least / t
