"""lm_tokens_per_s: every generated token over all of the window's time,
up to the end of the last batch started inside it."""


def read(run):
    rec = run.record
    return rec.units / rec.elapsed_s if rec.elapsed_s > 0 else None
