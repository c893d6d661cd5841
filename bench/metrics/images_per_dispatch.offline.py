"""Images served per engine dispatch: how full the batches the serve loop
hands the generator are (the engine's dispatch log)."""


def read(d: dict):
    n = d["driver"].get("dispatches")
    return d["driver"]["images"] / n if n else None
