"""The collectives' share of the device's busy time over the traced span,
in percent: of the trace's ten largest device operations
(``trace_reduce.reduce``'s ``device_ops``, seconds a device), those named
as a collective, over the busy seconds.  ``trace_reduce.short_name`` keeps
an operation's own name and drops its opcode, and XLA names an operation
after the jax primitive it came from where it has one: the ``psum`` of a
``shard_map`` body is ``%psum_invariant.7`` in a v5e trace, not
``%all-reduce.7`` (my chip runs, PR 35).  So both vocabularies count:
XLA's opcodes and jax's collective primitives.  0.0 where none is among
the ten: each is then smaller than the tenth.  No trace, or one in which
nothing ran on a device: nothing."""

COLLECTIVES = (
    # XLA's opcodes (an unnamed or renamed instruction keeps them)
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
    "all-to-all", "collective-broadcast",
    # jax's primitives, with and without the _invariant suffix
    "psum", "pmax", "pmin", "all_gather", "reduce_scatter", "ppermute",
    "all_to_all", "pbroadcast")


def read(spec: dict, ctx: dict):
    t = ctx.get("trace")
    if not t or t["busy_s"] <= 0:
        return None
    seconds = sum(s for name, s in t["device_ops"]
                  if name.lstrip("%").startswith(COLLECTIVES))
    return 100.0 * seconds / t["busy_s"]
