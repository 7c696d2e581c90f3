"""Analytic operation and byte counts of one Hetero-SplitEE round on the
Table-I ResNet-18, from the configuration's shapes and each client's cut.

Counted: every convolution and the two fully connected heads, forward and
backward, at 2 operations per multiply-add.  A convolution counts the
taps of its kernel that fall on the input, not those on its zero padding
(XLA's own count does the same).  A convolution's backward is
its weight gradient plus, where its input carries a gradient, its input
gradient, each as many operations as the forward.  The client's first
convolution reads the images and the server's first block reads ``h``
through ``stop_gradient``, so those convolutions get no input gradient.
BatchNorm, ReLU, pooling, the loss, Adam and Eq. (1) are elementwise and
are not counted; nothing is recomputed, so nothing recomputed is counted.

Bytes are those of the convolutions alone, for their roofline: each pass
reads its two operands and writes its result once, in float32, the type
the program stores them in.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Sequence

F32 = 4


@dataclass(frozen=True)
class Conv:
    hin: int
    hout: int
    k: int
    cin: int
    cout: int
    #: the convolution's input is the segment's input (no input gradient)
    first: bool

    @property
    def stride(self) -> int:
        return self.hin // self.hout

    def taps(self) -> int:
        """(output position, kernel tap) pairs on the input, per axis, of a
        ``SAME`` convolution."""
        pad = max((self.hout - 1) * self.stride + self.k - self.hin, 0) // 2
        return sum(1 for o in range(self.hout) for t in range(self.k)
                   if 0 <= o * self.stride + t - pad < self.hin)

    def flops(self, batch: int) -> int:
        return 2 * batch * self.taps() ** 2 * self.cin * self.cout

    def bytes(self, batch: int) -> int:
        x = batch * self.hin * self.hin * self.cin * F32
        y = batch * self.hout * self.hout * self.cout * F32
        w = self.k * self.k * self.cin * self.cout * F32
        fwd = x + w + y
        wgrad = x + y + w
        dgrad = 0 if self.first else y + w + x
        return fwd + wgrad + dgrad

    def train_flops(self, batch: int) -> int:
        return self.flops(batch) * (2 if self.first else 3)


def convs(channels: Sequence[int], strides: Sequence[int], image_size: int,
          start: int, end: int) -> List[Conv]:
    """The convolutions of layers start+1..end (1-indexed, as the paper)."""
    size = image_size
    for i in range(start):
        size //= strides[i]
    out: List[Conv] = []
    for i in range(start, end):
        first = i == start
        cin = 3 if i == 0 else channels[i - 1]
        cout = channels[i]
        hout = size // strides[i]
        if i == 0:
            out.append(Conv(size, hout, 3, cin, cout, first))
        else:
            out.append(Conv(size, hout, 3, cin, cout, first))
            out.append(Conv(hout, hout, 3, cout, cout, False))
            if cin != cout:
                out.append(Conv(size, hout, 1, cin, cout, first))
        size = hout
    return out


@dataclass(frozen=True)
class RoundCount:
    #: model operations of one round, forward and backward
    flops: int
    #: the convolutions' share of ``flops``
    conv_flops: int
    #: bytes the convolutions read and write in one round
    conv_bytes: int


def round_count(net: dict, job: dict) -> RoundCount:
    """``net``: channels, strides, image_size, num_classes.  ``job``:
    splits, batch, local_epochs."""
    ch, st, size = net["channels"], net["strides"], net["image_size"]
    classes, batch = net["num_classes"], job["batch"]
    n_layers = len(ch)
    conv_f = conv_b = fc_f = 0
    for li in job["splits"]:
        for seg in (convs(ch, st, size, 0, li),
                    convs(ch, st, size, li, n_layers)):
            conv_f += sum(c.train_flops(batch) for c in seg)
            conv_b += sum(c.bytes(batch) for c in seg)
        # exit head at the cut and server head: forward, weight and input
        # gradients
        fc_f += 3 * 2 * batch * (ch[li - 1] + ch[-1]) * classes
    reps = job["local_epochs"]
    return RoundCount(flops=reps * (conv_f + fc_f), conv_flops=reps * conv_f,
                      conv_bytes=reps * conv_b)
