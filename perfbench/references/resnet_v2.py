"""ResNet v2 (pre-activation bottleneck, depth 9n+2), plain float32 forward
with training-mode batch norm.

After the Keras CIFAR ResNet v2 that the MPI4DL reference's
``src/models/resnet.py`` ports (``get_resnet_v2``; its benchmarks fix
n = 12, ResNet-110): a 3x3 stem to 16 filters with bn and relu, three
stages of n blocks (widths 16->64, 64->128, 128->256; stages two and three
open with stride 2), then bn, relu, an 8x8 average pool, flatten, dense.
A block is bn-relu-conv3x3, bn-relu-conv3x3, bn-relu-conv1x1 plus the
input, which the first block of a stage projects with a 1x1 convolution;
the very first block has no bn-relu before its first convolution.  That
source's two 3x3 convolutions (where Keras has 1x1, 3x3) and its flattening
head (no global pool: the dense layer grows with the image) are kept,
because they are the shapes the reference's charts were made with.

Departure, the program's: logits go to the loss without the source's
softmax inside the model.

Weights are the program's parameter tree: a list with one entry per cell.
"""

from __future__ import annotations

from perfbench.references import plain
from perfbench.references.plain import Tally


def _pre_act_conv(x, p, stride, padding, tally):
    """bn-relu-conv, or a bare convolution where ``p`` holds only it."""
    if len(p) == 3:
        x = plain.relu(plain.batchnorm_train(x, p[0]))
    return plain.conv(x, p[-1], stride, padding, tally)


def _block(p, x, stride, tally):
    y = _pre_act_conv(x, p["r1"], stride, 1, tally)
    y = _pre_act_conv(y, p["r2"], 1, 1, tally)
    y = _pre_act_conv(y, p["r3"], 1, 0, tally)
    if "r4" in p:
        x = plain.conv(x, p["r4"][0], stride, 0, tally)
    return x + y


def cells(params, sizes, tally: Tally | None = None):
    """ResNet-(9n+2) v2, n = ``sizes["num_layers"]``, as one function per cell
    of the program's model, each from the activation before it to the one
    after; the last gives the logits."""
    n = sizes["num_layers"]
    assert len(params) == 3 * n + 2, (len(params), n)

    def stem(x):
        p = params[0]
        return plain.relu(plain.batchnorm_train(
            plain.conv(x, p[0], 1, 1, tally), p[1]))

    def block(i, p):
        stage, k = divmod(i, n)
        return lambda x: _block(p, x, 2 if (stage > 0 and k == 0) else 1, tally)

    def head(x):
        p = params[-1]
        x = plain.avg_pool(plain.relu(plain.batchnorm_train(x, p[0])), 8, 8)
        return plain.dense(x.reshape(x.shape[0], -1), p[-1], tally)

    return [stem] + [block(i, p) for i, p in enumerate(params[1:-1])] + [head]
