"""Mean fill of the server's micro-batches, in % of the bucket each was
padded to: the sum over the count of the server's own
``serve.batch_fill`` histogram, recorded while the window ran."""

NEEDS_OBS = True


def read(ctx):
    hist = (ctx.obs or {}).get("histograms", {}).get("serve.batch_fill")
    if not hist or not hist["count"]:
        return None
    return 100.0 * hist["sum"] / hist["count"]
