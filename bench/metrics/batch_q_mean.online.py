"""Mean queries per micro-batch the queue formed in the open-loop
window: `AsyncBatchQueue.stats()` queries over batches."""


def read(ctx):
    s = ctx.queue_stats
    if ctx.kind != "open" or not s or not s.get("batches"):
        return None
    return s["queries"] / s["batches"]
