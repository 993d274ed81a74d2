"""Mean share of a cohort's slots holding a tenant, over its waves
(the ``cohort.wave`` span's ``slots_active`` over ``n_slots``)."""


def read(ctx):
    occ = [s["slots_active"] for s in ctx.spans
           if s.get("name") == "cohort.wave" and "slots_active" in s]
    if not occ:
        return None
    return 100.0 * sum(occ) / len(occ) / ctx.traffic["n_slots"]
