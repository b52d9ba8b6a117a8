"""Process start -> the first measured step or request, compilation included (host clock)."""
def read(ctx):
    return ctx["setup_s"]
