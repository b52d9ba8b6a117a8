"""Seconds of set-up in which some program was read from the persistent compilation cache and loaded: the union of the ledger's `cache_read` records in set-up (program counter)."""
import setup_lib


def read(ctx):
    return setup_lib.union_s(ctx, ("cache_read",))
