"""Shared by the host_tail_share readers: the fused loop's host tail
(host_sec_total) over host tail plus device calls
(device_call_sec_total), summed over the window's requests, in %."""


def share(ctx):
    done = [r for r in ctx["records"]
            if "error" not in r and r.get("host_sec") is not None
            and r.get("device_call_sec") is not None]
    host = sum(r["host_sec"] for r in done)
    dev = sum(r["device_call_sec"] for r in done)
    if dev <= 0:
        return None
    return 100.0 * host / (host + dev)
