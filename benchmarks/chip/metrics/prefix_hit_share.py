"""Share of the tokens handed to the engine's prefill in the window
(``engine/prefill_tokens``: prompts, observations and swap-ins, shared or
not) that prefix sharing served from blocks already cached
(``engine/shared_prefix_tokens``: a group's repeated prompt and radix
hits), from the program's counters at the window's bounds."""
import harness as H


def read(rec):
    fed = H.program_delta(rec, "engine/prefill_tokens")
    if fed <= 0:
        return None
    return 100.0 * H.program_delta(rec, "engine/shared_prefix_tokens") / fed
