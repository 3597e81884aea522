"""The reference's ``benchmark/paddle/rnn/rnn.py`` net: embedding 128 ->
fc 4h -> lstmemory(h) -> last_seq -> fc 2 softmax -> classification cost
over a two-class (IMDB-style) label."""

from __future__ import annotations

from paddle_tpu.layers import activation as act
from paddle_tpu.layers import api as layer
from paddle_tpu.layers import data_type


def lstm_classify_cost(hidden, vocab=30000, embed=128):
    data = layer.data(name="data",
                      type=data_type.integer_value_sequence(vocab))
    net = layer.embedding(input=data, size=embed)
    net = layer.fc(input=net, size=hidden * 4, act=act.LinearActivation())
    net = layer.lstmemory(input=net)
    net = layer.last_seq(input=net)
    net = layer.fc(input=net, size=2, act=act.SoftmaxActivation())
    label = layer.data(name="label", type=data_type.integer_value(2))
    return layer.classification_cost(input=net, label=label)
