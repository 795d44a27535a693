"""The share of the router's assignments that landed on the experts this chip holds:
``moe_rows_here`` over tokens x ``num_experts_per_tok``, the mean over layers and the
window's steps (the expert layers' own device scalar, fetched after the window). 6.25 at an
even router over 32 of 512 experts."""


def read(record):
    share = (record.get("moe") or {}).get("rows_here_share")
    return None if share is None else 100.0 * share
