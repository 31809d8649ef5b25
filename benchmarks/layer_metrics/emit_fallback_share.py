"""emit_fallback_share (%): of the rooms whose broadcast update a flush
encoded, those that left the one native call (`ymx_encode_steps_many`)
for the per-room encoder: `emit_fallback` over `emit_batched +
emit_fallback` of the engine's flush metrics, summed by the generator
over the window's flushes.  0 where every typed room stays in the batch.
Source: program_counter; nothing where the generator sums no such
counters or no room was encoded."""


def read(trace, counters):
    if "emit_fallback" not in counters:
        return None
    encoded = counters.get("emit_batched", 0) + counters["emit_fallback"]
    if not encoded:
        return None
    return 100.0 * counters["emit_fallback"] / encoded
