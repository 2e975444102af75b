"""Work of one toy step on a batch: a multiply, an add and a remainder
per token, each int32 token read and written once."""


def work(shape, cfg):
    (batch,) = shape
    return 3 * batch, 8 * batch
