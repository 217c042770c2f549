"""A value of the client's view of the window (`lib/loadgen.summarize`)."""


def read(ctx, key):
    return ctx["result"].get("summary", {}).get(key)
