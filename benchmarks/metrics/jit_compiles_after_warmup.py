"""Programs compiled inside the window (`EngineStats.jit_compiles_after_warmup`,
counted by the program's recompile witness); has to read 0."""


def read(ctx):
    return ctx.counters.get("jit_compiles_after_warmup")
