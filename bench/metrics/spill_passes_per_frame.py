"""Mean of the served frames' `spill_passes` counter over the window: how
many k_pass-slot passes carried entries (the program's own counter)."""


def read(run):
    served = run.served
    if not served:
        return None
    return sum(float(r.counters["spill_passes"]) for r in served) \
        / len(served)
