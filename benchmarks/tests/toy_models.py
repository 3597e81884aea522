"""Toy-size models for the CPU tests of the harness, built from the
program's own layers (the timed sizes live in ``benchmarks/configs``)."""


def toy_resnet_cost():
    """A bottleneck ResNet on 32x32x3 / 10 classes with the stage table
    of ``data/configs/resnet_toy.json``."""
    from paddle_tpu.layers import activation as act
    from paddle_tpu.layers import api as layer
    from paddle_tpu.layers import data_type, pooling
    from paddle_tpu.models import image as zoo

    img = zoo._img_data(32, 32)
    tmp = zoo._conv_bn("conv1", img, 7, 8, 2, 3, channels=3)
    tmp = layer.img_pool(name="pool1", input=tmp, pool_size=3, stride=2)
    for sname, num, f1, f2, stride in (("res2", 1, 8, 16, 1),
                                       ("res3", 2, 8, 32, 2)):
        tmp = zoo._mid_projection(f"{sname}_1", tmp, f1, f2, stride=stride)
        for i in range(2, num + 1):
            tmp = zoo._bottleneck(f"{sname}_{i}", tmp, f1, f2)
    tmp = layer.img_pool(name="avgpool", input=tmp, pool_size=4, stride=1,
                         pool_type=pooling.AvgPooling())
    predict = layer.fc(input=tmp, size=10, act=act.SoftmaxActivation(),
                       name="fc_out")
    label = layer.data(name="label", type=data_type.integer_value(10))
    return layer.cross_entropy_cost(input=predict, label=label, name="loss")


def toy_mlp_cost():
    """A two-layer perceptron on 16-wide vectors / 4 classes: a model
    family the benchmark has no cell of, for the files-only test."""
    from paddle_tpu.layers import activation as act
    from paddle_tpu.layers import api as layer
    from paddle_tpu.layers import data_type

    x = layer.data(name="x", type=data_type.dense_vector(16))
    h = layer.fc(input=x, size=32, act=act.ReluActivation(), name="h1")
    predict = layer.fc(input=h, size=4, act=act.SoftmaxActivation(),
                       name="out")
    label = layer.data(name="label", type=data_type.integer_value(4))
    return layer.cross_entropy_cost(input=predict, label=label, name="loss")
