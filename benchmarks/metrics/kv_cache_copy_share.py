"""KV residency: share of the device's busy time spent in operations whose
result has the whole cache's shape (layers x lanes x context x kv heads x head
size, in the cache's type): the contiguous cache copied or rewritten whole."""

_SHORT = {"bfloat16": "bf16", "float32": "f32", "float16": "f16"}


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr["busy_s"]:
        return None
    c = ctx.config
    shape = (f"{_SHORT[ctx.kv_dtype]}[{c.n_layers},{ctx.lanes},{c.seq_len},"
             f"{c.n_kv_heads},{c.head_size}]")
    secs = sum(v for (_name, s), v in tr["op_seconds"].items() if s == shape)
    return 100.0 * secs / tr["busy_s"]
