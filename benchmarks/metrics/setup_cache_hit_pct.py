"""Programs served from the persistent compilation cache over the programs that asked it, in set-up: the ledger's `cache_hit` over its `cache_request` records; None where no program asked (program counter)."""
import setup_lib


def read(ctx):
    asked = setup_lib.count(ctx, "cache_request")
    if not asked:
        return None
    return 100.0 * setup_lib.count(ctx, "cache_hit") / asked
