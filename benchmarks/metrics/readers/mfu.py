"""Model utilisation: operations the forward and backward passes
require per item (from the configuration's shapes, recomputation not
counted) times items per second per chip, over the chip's peak."""

from harness import opsbytes


def read(ctx):
    if ctx["peaks"] is None:
        return None
    cell = ctx["cell"]
    flops = getattr(opsbytes, cell.config["flops_per_item"])(
        cell.config, cell.spec["data"]
    )
    rate = ctx["values"]["train_items_per_s_chip"]
    return 100.0 * flops * rate / ctx["peaks"]["flops_bf16"]
