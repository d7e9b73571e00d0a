"""A reader of a kind of its own, found by its file's name: the share of
the model's matrix-product operations a token that the experts take. It
asks the cell's family for both counts, as a kernel's roofline reader asks
it for the kernel's operations and bytes. A count, so a CPU run may give it.
"""


def wants(spec: dict) -> list:
    return [spec["seen"]]


def read(spec: dict, run: dict):
    family, cfg = run["family"], run["cfg"]
    if not hasattr(family, "expert_params"):
        return None
    if not run["edges"].delta(spec["seen"]):     # no token went through
        return None
    return (100.0 * cfg["num_hidden_layers"] * family.expert_params(cfg)
            / family.matmul_params(cfg))
