"""Process start to the opening of the window: imports, backend start,
compile-cache loads (compilation in a first run), state init, warm-up."""


def read(record):
    t0 = record["setup"].get("window_t0")
    return {"value": t0 - record["t_start"]} if t0 is not None else None
