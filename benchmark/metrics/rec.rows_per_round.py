"""Log + replay + model, record store: distinct (key, field) rows a
combiner round merges, over the window: delta of the program's
`nr.rec.window_rows` over delta of `nr.exec.rounds`. Zipfian hot keys
collapse many updates into one row. None where the program does not
count rows."""


def read(ctx):
    reg = ctx.get("reg", {})
    rows, rounds = reg.get("nr.rec.window_rows"), reg.get("nr.exec.rounds")
    return rows / rounds if rows is not None and rounds else None
