"""How far a counter of the engine moved over the window
(`LLMServer.metrics()[<counter>]`, after minus before)."""


def read(ctx, counter):
    c = ctx["result"].get("counters")
    if not c or counter not in c["after"] or counter not in c["before"]:
        return None
    return c["after"][counter] - c["before"][counter]
