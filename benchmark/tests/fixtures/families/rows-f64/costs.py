"""Operations and bytes of one update of the fixture family: softmax
regression, k gradient steps of two [b,f]x[f,c] products and the final
loss's one, the [b,f] slab read once a product."""


def update(cfg):
    m = cfg.model
    b, f, c, k = (cfg.buffer.max_size, m.num_features, m.num_classes + 1,
                  m.num_max_iter)
    return (2 * k + 1) * 2.0 * b * f * c, (2 * k + 1) * b * f * 4.0


def evaluation(cfg, test):
    m = cfg.model
    n, f, c = len(test[1]), m.num_features, m.num_classes + 1
    return 2.0 * n * f * c, n * f * 4.0 + f * c * 4.0
