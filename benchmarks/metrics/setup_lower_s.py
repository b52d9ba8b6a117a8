"""Seconds of set-up in which some program, eager ones included, was traced or lowered: the union of the ledger's `trace` and `lower` records in set-up, the part no compile cache saves (program counter)."""
import setup_lib


def read(ctx):
    return setup_lib.union_s(ctx, ("trace", "lower"))
