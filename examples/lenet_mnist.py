"""LeNet on MNIST — the reference's canonical first example
(org.deeplearning4j.examples LeNetMNIST), TPU-native.

Run: JAX_PLATFORMS=cpu python examples/lenet_mnist.py   (on the chip: unset)
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deeplearning4j_tpu.data.mnist import MnistDataSetIterator
from deeplearning4j_tpu.model.zoo import LeNet
from deeplearning4j_tpu.train.solver import Solver
from deeplearning4j_tpu.train.evaluation import Evaluation


def main():
    model = LeNet(seed=123).init()
    train_iter = MnistDataSetIterator(64, train=True, num_examples=2048)
    test_iter = MnistDataSetIterator(256, train=False, num_examples=512)

    solver = Solver(model)
    for epoch in range(2):
        score = None
        for ds in train_iter:
            score, _ = solver.fit_batch(ds.features, ds.labels)
        train_iter.reset()
        print(f"epoch {epoch}: score={float(score):.4f}")

    ev = Evaluation(num_classes=10)
    for ds in test_iter:
        ev.eval(ds.labels, model.output(ds.features))
    print(ev.stats())


if __name__ == "__main__":
    main()
