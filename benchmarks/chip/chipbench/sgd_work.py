"""Operations and bytes of the gradient of one member's SGD step (Alg. 2,
line 13), counted as ``chipbench.work`` counts, from shapes alone: the
features again (the loss runs the conv forward on the batch a second
time), the conv backward, and the ELM loss gradient's products Hβ and
(Hβ − T)βᵀ, 4·B·L·C, which read H, β and T and write dL/dH. The least
time of this work is what ``sgd_update_roofline.sgd`` holds the device
time of the program's ``sgd_update`` scope to."""
from __future__ import annotations

from typing import Dict

from chipbench import work


def grad(model: dict, batch: int) -> Dict[str, float]:
    fwd = work.conv_forward(model, batch)
    bwd = work.conv_backward(model, batch)
    L, C = work.feature_dim(model), model["num_classes"]
    loss = {"flops": 4.0 * batch * L * C,
            "bytes": work.F32 * (2 * batch * L + L * C + batch * C)}
    return {k: fwd[k] + bwd[k] + loss[k] for k in ("flops", "bytes")}
