"""Operations a ResNet-50 needs, from its shapes (He et al. 2015 table
1): multiply-accumulates of every convolution and of the classifier,
two operations each, forward; a training step needs three times the
forward (the backward computes a gradient for the input and one for the
weights of every layer). Normalisation, activations and pooling are left
out, as is usual for a model's FLOPs."""

STAGES = ((64, 3), (128, 4), (256, 6), (512, 3))


def forward_flops_per_item(cfg) -> float:
    size = cfg["image_size"] // 2              # 7x7 stride 2
    macs = size * size * 7 * 7 * cfg["in_channels"] * 64
    size //= 2                                 # 3x3 max pool stride 2
    n_in = 64
    for s, (mid, reps) in enumerate(STAGES):
        n_out = 4 * mid
        for b in range(reps):
            stride = 2 if (s > 0 and b == 0) else 1
            macs += size * size * n_in * mid   # 1x1, before the stride
            size //= stride
            macs += size * size * 9 * mid * mid + size * size * mid * n_out
            if b == 0:
                macs += size * size * n_in * n_out
            n_in = n_out
    macs += n_in * cfg["num_classes"]
    return 2.0 * macs


def train_flops_per_item(cfg, mix) -> float:
    return 3.0 * forward_flops_per_item(cfg)
