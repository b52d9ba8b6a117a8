"""Seconds of set-up in which XLA compiled some program, eager ones included: the union of the ledger's `xla` records in set-up (program counter)."""
import setup_lib


def read(ctx):
    return setup_lib.union_s(ctx, ("xla",))
