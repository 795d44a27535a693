"""The busiest expert's assignments over the mean, the worst layer of a step, averaged
over the window's steps: the expert layers' own device scalar, fetched after the window."""


def read(record):
    return (record.get("moe") or {}).get("load_max_over_mean")
