"""Least HBM bytes of one WordCount map task: the block read once, the
vocabulary of int32 counts written once."""


def least_bytes(block: dict, config: dict) -> int:
    return int(block["tokens"].nbytes) + 4 * int(config["vocab"])
