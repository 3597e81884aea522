"""Fluid model-level e2e parity — ports of the four
reference composition tests that exercise fluid layers + Executor as
whole models, on the hermetic datasets:

- ``test_word2vec.py`` (shared-name embeddings, concat, N-gram LM)
- ``test_understand_sentiment_lstm.py`` (embedding -> reshape ->
  transpose -> StaticRNN lstm -> fc, the layers.lstm path)
- ``test_recommender_system.py`` (9 inputs, shared feature towers,
  sequence_pool + sequence_conv_pool over LoD inputs, cos_sim)
- ``test_image_classification_train.py`` (resnet_cifar10 +
  vgg16_bn_drop via conv2d/batch_norm/img_conv_group)

Success criteria mirror the references: decreasing loss (word2vec's
"cost < 10", recommender's "cost < 6") or batches completing with
finite metrics (image classification's two-minibatch criterion).
"""

from __future__ import annotations

import numpy as np

from paddle_tpu import fluid
from paddle_tpu.fluid import framework, layers, nets


def _reset():
    framework.reset_default_programs()


def _startup(exe):
    exe.run(fluid.default_startup_program(), feed={}, fetch_list=[])


def test_word2vec_ngram_lm_trains():
    """≅ test_word2vec.py:1-165 on the hermetic imikolov."""
    import paddle_tpu as paddle

    _reset()
    embed_size, hidden_size, N, batch_size = 32, 256, 5, 32
    word_dict = paddle.dataset.imikolov.build_dict()
    dict_size = len(word_dict)

    words = [layers.data(name=n, shape=[1], dtype="int64")
             for n in ("firstw", "secondw", "thirdw", "forthw", "nextw")]
    embeds = [layers.embedding(
        input=w, size=[dict_size, embed_size], dtype="float32",
        is_sparse=True, param_attr={"name": "shared_w"})
        for w in words[:4]]
    concat_embed = layers.concat(input=embeds, axis=1)
    hidden1 = layers.fc(input=concat_embed, size=hidden_size, act="sigmoid")
    predict_word = layers.fc(input=hidden1, size=dict_size, act="softmax")
    cost = layers.cross_entropy(input=predict_word, label=words[4])
    avg_cost = layers.mean(cost)
    fluid.SGDOptimizer(learning_rate=0.1).minimize(avg_cost)

    # shared_w really is shared: the four embedding calls return the SAME
    # parameter object, every lookup reads it, and the program holds
    # exactly the expected parameter set (shared_w + 2 fc pairs)
    block = fluid.default_main_program().global_block()
    shared = [v for v in block.all_parameters() if v.name == "shared_w"]
    assert len(shared) == 1
    assert len(block.all_parameters()) == 5, sorted(
        p.name for p in block.all_parameters())
    lookup_ins = [op for op in block.ops if op.type == "lookup_table"]
    assert len(lookup_ins) == 4
    assert all(op.inputs["W"] == ["shared_w"] for op in lookup_ins)

    reader = paddle.reader.batch(paddle.dataset.imikolov.train(word_dict, N),
                                 batch_size)
    exe = fluid.Executor()
    _startup(exe)
    costs = []
    for epoch in range(3):
        for data in reader():
            cols = [np.asarray([row[i] for row in data],
                               np.int64)[:, None] for i in range(5)]
            feed = dict(zip(("firstw", "secondw", "thirdw", "forthw",
                             "nextw"), cols))
            (out,) = exe.run(feed=feed, fetch_list=[avg_cost])
            costs.append(float(out))
    assert np.isfinite(costs).all()
    assert costs[-1] < costs[0], (costs[0], costs[-1])
    assert costs[-1] < 10.0  # the reference's success criterion


def test_understand_sentiment_lstm_trains():
    """≅ test_understand_sentiment_lstm.py:12-41 (layers.lstm =
    StaticRNN + lstm_unit) on the hermetic imdb, seq chopped like
    chop_data."""
    import paddle_tpu as paddle

    _reset()
    word_dict = paddle.dataset.imdb.word_dict()
    dict_dim, class_dim, emb_dim = len(word_dict), 2, 32
    seq_len, batch_size = 32, 50

    data = layers.data(name="words", shape=[seq_len * batch_size, 1],
                       append_batch_size=False, dtype="int64")
    label = layers.data(name="label", shape=[batch_size, 1],
                        append_batch_size=False, dtype="int64")
    emb = layers.embedding(input=data, size=[dict_dim, emb_dim])
    emb = layers.reshape(x=emb, shape=[batch_size, seq_len, emb_dim])
    emb = layers.transpose(x=emb, axis=[1, 0, 2])
    c_pre_init = layers.fill_constant(dtype="float32",
                                      shape=[batch_size, emb_dim], value=0.0)
    layer_1_out = layers.lstm(emb, c_pre_init=c_pre_init, hidden_dim=emb_dim)
    layer_1_out = layers.transpose(x=layer_1_out, axis=[1, 0, 2])
    prediction = layers.fc(input=layer_1_out, size=class_dim, act="softmax")
    cost = layers.cross_entropy(input=prediction, label=label)
    avg_cost = layers.mean(cost)
    fluid.AdamOptimizer(learning_rate=0.002).minimize(avg_cost)
    acc = layers.accuracy(input=prediction, label=label)

    # chop_data: keep sequences >= seq_len, truncate, take batch_size
    rows = [(x[0][:seq_len], x[1])
            for x in paddle.dataset.imdb.train(word_dict)()
            if len(x[0]) >= seq_len][:batch_size]
    assert len(rows) == batch_size, "hermetic imdb too short for chop_data"
    words_np = np.concatenate([np.asarray(r[0], np.int64)
                               for r in rows]).reshape(-1, 1)
    label_np = np.asarray([r[1] for r in rows], np.int64).reshape(-1, 1)

    exe = fluid.Executor()
    _startup(exe)
    accs = []
    for it in range(40):
        c, a = exe.run(feed={"words": words_np, "label": label_np},
                       fetch_list=[avg_cost, acc])
        accs.append(float(a))
        if accs[-1] > 0.9:  # the reference's stopping criterion
            break
    assert accs[-1] > 0.9, accs[-5:]


def test_recommender_system_trains():
    """≅ test_recommender_system.py:1-315 on the hermetic movielens:
    7 id towers, LoD category/title inputs through sequence_pool and
    nets.sequence_conv_pool, cos_sim head, square_error_cost."""
    import paddle_tpu as paddle
    from paddle_tpu.core.lod import from_ragged

    _reset()
    ml = paddle.dataset.movielens
    is_sparse = True

    def usr_combined():
        uid = layers.data(name="user_id", shape=[1], dtype="int64")
        usr_emb = layers.embedding(
            input=uid, dtype="float32", size=[ml.max_user_id() + 1, 32],
            param_attr={"name": "user_table"}, is_sparse=is_sparse)
        usr_fc = layers.fc(input=usr_emb, size=32)
        gid = layers.data(name="gender_id", shape=[1], dtype="int64")
        g_emb = layers.embedding(input=gid, size=[2, 16],
                                 param_attr={"name": "gender_table"},
                                 is_sparse=is_sparse)
        g_fc = layers.fc(input=g_emb, size=16)
        aid = layers.data(name="age_id", shape=[1], dtype="int64")
        a_emb = layers.embedding(input=aid, size=[len(ml.age_table), 16],
                                 param_attr={"name": "age_table"},
                                 is_sparse=is_sparse)
        a_fc = layers.fc(input=a_emb, size=16)
        jid = layers.data(name="job_id", shape=[1], dtype="int64")
        j_emb = layers.embedding(input=jid, size=[ml.max_job_id() + 1, 16],
                                 param_attr={"name": "job_table"},
                                 is_sparse=is_sparse)
        j_fc = layers.fc(input=j_emb, size=16)
        cat = layers.concat(input=[usr_fc, g_fc, a_fc, j_fc], axis=1)
        return layers.fc(input=cat, size=200, act="tanh")

    def mov_combined():
        mid = layers.data(name="movie_id", shape=[1], dtype="int64")
        m_emb = layers.embedding(
            input=mid, dtype="float32", size=[ml.max_movie_id() + 1, 32],
            param_attr={"name": "movie_table"}, is_sparse=is_sparse)
        m_fc = layers.fc(input=m_emb, size=32)
        cid = layers.data(name="category_id", shape=[1], dtype="int64",
                          lod_level=1)
        c_emb = layers.embedding(input=cid,
                                 size=[len(ml.movie_categories()), 32],
                                 is_sparse=is_sparse)
        c_hidden = layers.sequence_pool(input=c_emb, pool_type="sum")
        tid = layers.data(name="movie_title", shape=[1], dtype="int64",
                          lod_level=1)
        t_emb = layers.embedding(input=tid,
                                 size=[len(ml.get_movie_title_dict()), 32],
                                 is_sparse=is_sparse)
        t_conv = nets.sequence_conv_pool(input=t_emb, num_filters=32,
                                         filter_size=3, act="tanh",
                                         pool_type="sum")
        cat = layers.concat(input=[m_fc, c_hidden, t_conv], axis=1)
        return layers.fc(input=cat, size=200, act="tanh")

    inference = layers.cos_sim(X=usr_combined(), Y=mov_combined())
    score = layers.data(name="score", shape=[1], dtype="float32")
    cost = layers.square_error_cost(input=inference, label=score)
    avg_cost = layers.mean(cost)
    fluid.SGDOptimizer(learning_rate=0.2).minimize(avg_cost)

    reader = paddle.reader.batch(ml.train(), batch_size=64)
    exe = fluid.Executor()
    _startup(exe)

    def func_feed(data):
        feed = {}
        for key, idx in (("user_id", 0), ("gender_id", 1), ("age_id", 2),
                         ("job_id", 3), ("movie_id", 4), ("score", 7)):
            dt = np.float32 if key == "score" else np.int64
            feed[key] = np.asarray([row[idx] for row in data],
                                   dt).reshape(len(data), 1)
        for key, idx in (("category_id", 5), ("movie_title", 6)):
            feed[key] = from_ragged(
                [np.asarray(row[idx], np.int64)[:, None] for row in data])
        return feed

    costs = []
    for epoch in range(2):
        for data in reader():
            (out,) = exe.run(feed=func_feed(data), fetch_list=[avg_cost])
            costs.append(float(out))
    assert np.isfinite(costs).all()
    assert costs[-1] < costs[0], (costs[0], costs[-1])
    assert costs[-1] < 6.0  # the reference's success criterion


def _resnet_cifar10(input, depth=8):
    """≅ resnet_cifar10 (test_image_classification_train.py:12-127)."""

    def conv_bn_layer(input, ch_out, filter_size, stride, padding,
                      act="relu"):
        tmp = layers.conv2d(input=input, filter_size=filter_size,
                            num_filters=ch_out, stride=stride,
                            padding=padding, act=None, bias_attr=False)
        return layers.batch_norm(input=tmp, act=act)

    def shortcut(input, ch_in, ch_out, stride):
        if ch_in != ch_out:
            return conv_bn_layer(input, ch_out, 1, stride, 0, None)
        return input

    def basicblock(input, ch_in, ch_out, stride):
        tmp = conv_bn_layer(input, ch_out, 3, stride, 1)
        tmp = conv_bn_layer(tmp, ch_out, 3, 1, 1, act=None)
        short = shortcut(input, ch_in, ch_out, stride)
        return layers.elementwise_add(x=tmp, y=short, act="relu")

    def layer_warp(block_func, input, ch_in, ch_out, count, stride):
        tmp = block_func(input, ch_in, ch_out, stride)
        for _ in range(1, count):
            tmp = block_func(tmp, ch_out, ch_out, 1)
        return tmp

    assert (depth - 2) % 6 == 0
    n = (depth - 2) // 6
    conv1 = conv_bn_layer(input, 16, 3, 1, 1)
    res1 = layer_warp(basicblock, conv1, 16, 16, n, 1)
    res2 = layer_warp(basicblock, res1, 16, 32, n, 2)
    res3 = layer_warp(basicblock, res2, 32, 64, n, 2)
    return layers.pool2d(input=res3, pool_size=8, pool_type="avg",
                         pool_stride=1)


def _vgg16_bn_drop(input):
    """≅ vgg16_bn_drop (test_image_classification_train.py:130-192),
    narrowed channel widths for test runtime (structure identical)."""
    from paddle_tpu.fluid.initializer import XavierInitializer

    def conv_block(input, num_filter, groups, dropouts):
        return nets.img_conv_group(
            input=input, pool_size=2, pool_stride=2,
            conv_num_filter=[num_filter] * groups, conv_filter_size=3,
            conv_act="relu", conv_with_batchnorm=True,
            conv_batchnorm_drop_rate=dropouts, pool_type="max")

    conv1 = conv_block(input, 16, 2, [0.3, 0])
    conv2 = conv_block(conv1, 32, 2, [0.4, 0])
    conv3 = conv_block(conv2, 64, 3, [0.4, 0.4, 0])
    drop = layers.dropout(x=conv3, dropout_prob=0.5)
    fc1 = layers.fc(input=drop, size=64, act=None,
                    param_attr={"initializer": XavierInitializer()})
    reshape1 = layers.reshape(x=fc1, shape=[-1, 64, 1, 1])
    bn = layers.batch_norm(input=reshape1, act="relu")
    drop2 = layers.dropout(x=bn, dropout_prob=0.5)
    return layers.fc(input=drop2, size=64, act=None,
                     param_attr={"initializer": XavierInitializer()})


def _train_image_classifier(net_fn, batches=2, batch_size=16):
    rng = np.random.default_rng(0)
    classdim, data_shape = 10, [3, 32, 32]
    images = layers.data(name="pixel", shape=data_shape, dtype="float32")
    label = layers.data(name="label", shape=[1], dtype="int64")
    net = net_fn(images)
    predict = layers.fc(input=net, size=classdim, act="softmax")
    cost = layers.cross_entropy(input=predict, label=label)
    avg_cost = layers.mean(cost)
    accuracy = layers.accuracy(input=predict, label=label)
    fluid.AdamOptimizer(learning_rate=0.001).minimize(avg_cost)

    exe = fluid.Executor()
    _startup(exe)
    outs = []
    for _ in range(batches):
        img = rng.normal(size=(batch_size, 3, 32, 32)).astype(np.float32)
        lbl = rng.integers(0, classdim,
                           size=(batch_size, 1)).astype(np.int64)
        loss, acc = exe.run(feed={"pixel": img, "label": lbl},
                            fetch_list=[avg_cost, accuracy])
        outs.append((float(loss), float(acc)))
    return outs


def test_image_classification_resnet_two_batches():
    """The reference's success criterion: two minibatches train with
    finite loss/acc (test_image_classification_train.py:253-258)."""
    _reset()
    outs = _train_image_classifier(lambda im: _resnet_cifar10(im, depth=8))
    assert all(np.isfinite(l) for l, _ in outs), outs


def test_image_classification_vgg_two_batches():
    _reset()
    outs = _train_image_classifier(_vgg16_bn_drop, batches=2, batch_size=8)
    assert all(np.isfinite(l) for l, _ in outs), outs
