"""Least HBM bytes of one AVG map task: the three columns read once, one
float32 mean a group written once."""


def least_bytes(block: dict, config: dict) -> int:
    return int(sum(block[k].nbytes for k in ("values", "group", "select"))) \
        + 4 * int(config["n_groups"])
