"""Mean host time per decode iteration that the engine's loop spends
outside backend calls and outside waits for the next arrival: the §4 slot
tasks, §6 page partitions and runtime flushes."""


def read(rec, ctx):
    begin = rec["window"][0]
    calls = [(a, b) for a, b, *_ in rec["prefills"] + rec["decodes"]
             if a >= begin]
    dec = [1 for a, *_ in rec["decodes"] if a >= begin]
    if not calls or not dec:
        return None
    end = max(b for _, b in calls)
    inside = sum(b - a for a, b in calls)
    slept = sum(b - a for a, b in rec["sleeps"] if a >= begin and b <= end)
    return 1e3 * ((end - begin) - inside - slept) / len(dec)
