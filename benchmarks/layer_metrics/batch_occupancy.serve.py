"""Mean over the window's iterations of decoding slots over slots, from the log
``InferenceEngine.step()`` returns."""


def read(record):
    if record.get("kind") != "serve" or not record.get("decode_lanes"):
        return None
    lanes = record["decode_lanes"]
    return 100.0 * sum(lanes) / (len(lanes) * record["slots"])
