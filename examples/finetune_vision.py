"""High-level hapi training: Model.fit on a vision-zoo network.

Run: JAX_PLATFORMS=cpu python examples/finetune_vision.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

import paddle_tpu as paddle
from paddle_tpu import optimizer as opt
from paddle_tpu.io.dataset import Dataset
from paddle_tpu.vision.models import mobilenet_v3_small


class SyntheticImages(Dataset):
    def __init__(self, n=32):
        rng = np.random.RandomState(0)
        self.x = rng.rand(n, 3, 32, 32).astype("float32")
        self.y = rng.randint(0, 4, (n, 1))

    def __getitem__(self, i):
        return self.x[i], self.y[i]

    def __len__(self):
        return len(self.x)


def main():
    paddle.seed(0)
    net = mobilenet_v3_small(num_classes=4)
    model = paddle.Model(net)
    model.prepare(opt.Adam(1e-3, parameters=net.parameters()),
                  paddle.nn.CrossEntropyLoss(),
                  paddle.metric.Accuracy())
    model.fit(SyntheticImages(), epochs=1, batch_size=8, verbose=1)
    result = model.evaluate(SyntheticImages(16), batch_size=8, verbose=0)
    print("eval:", result)


if __name__ == "__main__":
    main()
