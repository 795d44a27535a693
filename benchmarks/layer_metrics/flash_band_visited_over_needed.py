"""The query-key pairs the flash kernel's tile schedule VISITS over the pairs the masks ALLOW,
summed over the model's calls (``flash_attention.band_pairs`` for the tiles ``_resolve`` chose,
put into the record by the runner: plain integers, nothing traced). 1.0 would be a schedule
that touches no masked pair; a band of 1024-tiles under a window of 1024 reads 2.00."""


def read(record):
    band = record.get("band") or {}
    if not band.get("needed"):
        return None
    return band["visited"] / band["needed"]
