"""Occupancy of the sharded engine's all_to_all successor exchange, in %:
the bytes of real candidates over the bytes transmitted, summed over the
window's requests (the program's ``accounting`` counters,
parallel/sharded.py).  The single-chip engine has no exchange."""


def read(ctx):
    done = [r for r in ctx["records"]
            if "error" not in r and r.get("exchange_bytes_total")]
    sent = sum(r["exchange_bytes_total"] for r in done)
    if sent <= 0:
        return None
    return 100.0 * sum(r["exchange_payload_bytes_total"] for r in done) / sent
