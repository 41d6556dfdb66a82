"""Host milliseconds the engine's scheduler spends admitting requests
and topping up KV blocks (`Engine.stats`: admit_host_s + topup_host_s)
per engine step of the traced run."""


def read(cell, trace, measured):
    st = measured.get("stats") or {}
    if not st.get("decode_steps"):
        return None
    return 1e3 * (st["admit_host_s"] + st["topup_host_s"]) \
        / st["decode_steps"]
