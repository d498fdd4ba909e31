"""Share of the verify program's device time spent in cross-chip
collectives: the device seconds of `jit_prog` ops whose name holds a
collective's, over the `jit_prog` module's seconds, both summed over the
chips.

A collective is named by its HLO opcode (`all-gather`, `all-reduce`,
`collective-permute`, `reduce-scatter`, `all-to-all`, with or without
`-start`/`-done`) or by the JAX primitive it was lowered from (`psum`,
`ppermute`): compiled for a v5e 2x2, the ring's verify sums the shards'
counts in an all-reduce the compiler names `psum.<n>`.

The ops come from the trace's `device_ops`, which holds only the ten
ops with the most device time (`tracing.reduce_events`' `top`): a
collective outside them is not counted.  None where the trace holds no
verify module."""

MODULES = ("jit_prog",)
COLLECTIVES = ("all-gather", "all-reduce", "collective-permute",
               "reduce-scatter", "all-to-all", "psum", "ppermute")


def read(run):
    if run.trace is None:
        return None
    module_s = sum(run.trace["module_s"].get(m, 0.0) for m in MODULES)
    if module_s <= 0:
        return None
    coll = sum(s for name, s in run.trace["device_ops"]
               if name.split("/", 1)[0] in MODULES
               and any(k in name.split("/", 1)[1] for k in COLLECTIVES))
    return coll / module_s
