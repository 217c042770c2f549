"""A ratio of how far counters of the engine moved over the window
(`LLMServer.metrics()`, after minus before): the `numerator` counters'
differences summed, less those of `minus` (a counter that lies inside one of
the numerator's: prefill inside admission), over the `denominator` counters'
differences summed, times `scale`. `per_engine_setting` names a key of the
configuration's `engine` group that multiplies the denominator (slot-steps
over steps x `max_num_seqs` is the batch occupancy). Nothing when a counter is
missing (a program from before the counter) or the denominator did not move."""


def read(ctx, numerator, denominator, scale=1.0, per_engine_setting=None, minus=()):
    c = ctx["result"].get("counters")
    names = (*numerator, *minus, *denominator)
    if not c or any(n not in c["before"] or n not in c["after"] for n in names):
        return None
    moved = {n: c["after"][n] - c["before"][n] for n in names}
    below = sum(moved[n] for n in denominator)
    if below <= 0:
        return None
    if per_engine_setting is not None:
        below *= ctx["config"]["engine"][per_engine_setting]
    above = sum(moved[n] for n in numerator) - sum(moved[n] for n in minus)
    return scale * above / below
