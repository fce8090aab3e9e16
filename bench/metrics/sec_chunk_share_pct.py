"""Share of the chunks flushed from the window's start to its last answer
that ran on secondary lanes (the sessions' ``sec_lane_flushes`` over
``chunks_flushed``)."""


def read(ctx):
    if not ctx["chunks"]:
        return None
    return 100.0 * ctx["sec_chunks"] / ctx["chunks"]
