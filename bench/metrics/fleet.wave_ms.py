"""Mean duration of a fleet wave (the ``cohort.wave`` span: admit, one
compiled step of ``rounds_per_wave`` rounds, harvest, finalize)."""


def read(ctx):
    waves = [s["dur_us"] for s in ctx.spans if s.get("name") == "cohort.wave"]
    return sum(waves) / len(waves) / 1e3 if waves else None
