"""Programs compiled, or loaded from the compilation cache (JAX's
`backend_compile_duration` events), that ended inside the measured window:
0 when set-up warmed every program."""


def read(rec):
    return rec.get("compiles_in_window")
