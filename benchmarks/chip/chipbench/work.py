"""Operations and bytes of each layer of a CNN-ELM job, from shapes alone,
and the table of chip peaks they are held against.

Counts are of the work the algorithm needs, unpadded (a 6-channel GEMM is
counted at 6 columns, not at the 128 the kernel pads it to), f32 (4
bytes), a multiply-add as two operations. Per member and batch of B
images at L = F features and C classes:

* conv forward, stage s: 2·B·OH·OW·k²·Cin·Cout; it reads the input and
  the kernel and writes the output once.
* conv backward (SGD): the kernel gradient of every stage (as many
  operations as its forward), the input gradient of every stage but the
  first (the image needs none).
* ELM statistics: U = HᵀH and V = HᵀT, 2·B·L² + 2·B·L·C; they read H
  and T once and write U and V.
* β solve: a Cholesky factor of the L x L system (L³/3) and two triangular
  solves (2·L²·C).
* the ELM loss gradient: Hβ and its transpose product, 4·B·L·C.

``step`` gives the useful work of one SGD step (features once, as the
paper's algorithm computes them) and, per layer, the work its ops run as
the program schedules them: the features for the statistics and again
inside the gradient.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import Dict, List

F32 = 4
PEAKS_FILE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


@dataclass(frozen=True)
class Conv:
    b: int
    h: int
    w: int
    cin: int
    cout: int
    k: int

    @property
    def oh(self) -> int:
        return self.h - self.k + 1

    @property
    def ow(self) -> int:
        return self.w - self.k + 1

    @property
    def flops(self) -> float:
        return 2.0 * self.b * self.oh * self.ow * self.k * self.k \
            * self.cin * self.cout

    @property
    def bytes(self) -> float:
        return F32 * (self.b * self.h * self.w * self.cin
                      + self.k * self.k * self.cin * self.cout
                      + self.b * self.oh * self.ow * self.cout)


def convs(model: dict, batch: int) -> List[Conv]:
    """The valid convolutions of one forward pass over ``batch`` images."""
    out, n, cin = [], model["image_size"], model["image_channels"]
    k, pool = model["cnn_kernel"], model["cnn_pool"]
    for cout in model["cnn_channels"]:
        out.append(Conv(batch, n, n, cin, cout, k))
        n, cin = (n - k + 1) // pool, cout
    return out


def feature_dim(model: dict) -> int:
    n = model["image_size"]
    for _ in model["cnn_channels"]:
        n = (n - model["cnn_kernel"] + 1) // model["cnn_pool"]
    return n * n * model["cnn_channels"][-1]


def stats(model: dict, rows: int) -> Dict[str, float]:
    L, C = feature_dim(model), model["num_classes"]
    return {"flops": 2.0 * rows * L * L + 2.0 * rows * L * C,
            "bytes": F32 * (rows * L + rows * C + L * L + L * C)}


def solve_flops(model: dict) -> float:
    L, C = feature_dim(model), model["num_classes"]
    return L ** 3 / 3.0 + 2.0 * L * L * C


def conv_backward(model: dict, batch: int) -> Dict[str, float]:
    """Kernel gradients of every stage, input gradients of stages >= 2;
    each reads and writes what its forward does."""
    flops = byts = 0.0
    for s, c in enumerate(convs(model, batch)):
        passes = 1 if s == 0 else 2
        flops += passes * c.flops
        byts += passes * c.bytes
    return {"flops": flops, "bytes": byts}


def conv_forward(model: dict, batch: int) -> Dict[str, float]:
    cs = convs(model, batch)
    return {"flops": sum(c.flops for c in cs),
            "bytes": sum(c.bytes for c in cs)}


def step(model: dict, batch: int, sgd: bool) -> Dict[str, float]:
    """One member's batch: the useful operations and, per layer, the
    operations and bytes its ops run as the program schedules them."""
    fwd, st = conv_forward(model, batch), stats(model, batch)
    L, C = feature_dim(model), model["num_classes"]
    out = {"useful_flops": fwd["flops"] + st["flops"],
           "conv_flops": fwd["flops"], "conv_bytes": fwd["bytes"],
           "stats_flops": st["flops"], "stats_bytes": st["bytes"]}
    if sgd:
        bwd = conv_backward(model, batch)
        out["useful_flops"] += (bwd["flops"] + solve_flops(model)
                                + 4.0 * batch * L * C)
        # the gradient runs the features again, then the backward
        out["conv_flops"] += fwd["flops"] + bwd["flops"]
        out["conv_bytes"] += fwd["bytes"] + bwd["bytes"]
    return out


def job(model: dict, members: int, batches: int, batch: int, epochs: int
        ) -> Dict[str, float]:
    """A whole Map+Reduce job: ``members`` x ``batches`` steps per epoch,
    ``epochs`` SGD epochs (0: one pass of statistics), a final β solve
    per member."""
    per = step(model, batch, sgd=epochs > 0)
    steps = members * batches * max(epochs, 1)
    out = {k: v * steps for k, v in per.items()}
    out["useful_flops"] += members * solve_flops(model)
    out["images"] = float(members * batches * batch * max(epochs, 1))
    return out


def peaks(device_kind: str, path: str = PEAKS_FILE) -> Dict[str, float]:
    """The chip's published peaks; a kind not in the table is an error."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}; add its published numbers")
    return table[device_kind]


def roofline_s(flops: float, byts: float, peak: Dict[str, float]):
    """(least time, the bound that sets it) on one chip."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = byts / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
