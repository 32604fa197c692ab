"""The paper's four evaluation networks (arXiv 2603.23882 §5.3) as
layer lists, at any square input resolution.

The benchmark builds every request's layer graph here, from the
published architectures, and hands the compiler plain copies of these
records; the reference re-derives each schedule from the same records.
INT8 weights and activations throughout (§5.1).
"""

from __future__ import annotations

import dataclasses
import math


@dataclasses.dataclass(frozen=True)
class Layer:
    """Workload of one scheduled layer, in the field names the
    compiler's ``LayerSpec`` takes."""

    name: str
    kind: str
    macs: int
    weight_bytes: int
    act_in_bytes: int
    act_out_bytes: int
    p_out: int = 0
    c_out: int = 0
    c_in: int = 0
    kernel: int = 1


def conv(name, h, w, c_in, c_out, k, stride=1) -> Layer:
    p = math.ceil(h / stride) * math.ceil(w / stride)
    return Layer(name, "conv", p * c_out * c_in * k * k, c_out * c_in * k * k,
                 h * w * c_in, p * c_out, p, c_out, c_in, k)


def dwconv(name, h, w, c, k, stride=1) -> Layer:
    p = math.ceil(h / stride) * math.ceil(w / stride)
    return Layer(name, "dwconv", p * c * k * k, c * k * k, h * w * c, p * c,
                 p, c, 1, k)


def fc(name, c_in, c_out) -> Layer:
    return Layer(name, "fc", c_in * c_out, c_in * c_out, c_in, c_out,
                 1, c_out, c_in, 1)


def attention(name, tokens, d, n_heads, d_ff=0) -> Layer:
    macs = 4 * tokens * d * d + 2 * tokens * tokens * d \
        + 2 * tokens * d * d_ff
    return Layer(name, "attn", macs, 4 * d * d + 2 * d * d_ff, tokens * d,
                 tokens * d, tokens, d, d, 1)


def pool(name, h, w, c, k, stride=2) -> Layer:
    ho, wo = math.ceil(h / stride), math.ceil(w / stride)
    return Layer(name, "pool", 0, 0, h * w * c, ho * wo * c, ho * wo, c, c, k)


def eltwise(name, h, w, c) -> Layer:
    return Layer(name, "eltwise", 0, 0, 2 * h * w * c, h * w * c, h * w, c,
                 c, 1)


def squeezenet_1_1(hw: int) -> list[Layer]:
    """conv1 + 8 Fire modules (squeeze, expand 1x1, expand 3x3) + conv10."""
    out = [conv("conv1", hw, hw, 3, 64, 3, stride=2)]
    hw //= 4                                  # stride 2, then maxpool1
    c = 64

    def fire(i, s, e):
        out.extend([conv(f"fire{i}/squeeze1x1", hw, hw, c, s, 1),
                    conv(f"fire{i}/expand1x1", hw, hw, s, e, 1),
                    conv(f"fire{i}/expand3x3", hw, hw, s, e, 3)])
        return 2 * e

    c = fire(2, 16, 64)
    c = fire(3, 16, 64)
    hw //= 2
    c = fire(4, 32, 128)
    c = fire(5, 32, 128)
    hw //= 2
    for i, (s, e) in zip((6, 7, 8, 9), ((48, 192), (48, 192), (64, 256),
                                        (64, 256))):
        c = fire(i, s, e)
    out.append(conv("conv10", hw, hw, c, 1000, 1))
    return out


# kernel, expansion, out channels, squeeze-excite, stride (Howard et al.,
# MobileNetV3-Small, table 2)
_MBV3_SMALL = ((3, 16, 16, True, 2), (3, 72, 24, False, 2),
               (3, 88, 24, False, 1), (5, 96, 40, True, 2),
               (5, 240, 40, True, 1), (5, 240, 40, True, 1),
               (5, 120, 48, True, 1), (5, 144, 48, True, 1),
               (5, 288, 96, True, 2), (5, 576, 96, True, 1),
               (5, 576, 96, True, 1))


def mobilenetv3_small(hw: int) -> list[Layer]:
    """stem + 11 inverted-residual blocks (expand/dw/SE/project) + head."""
    out = [conv("stem", hw, hw, 3, 16, 3, stride=2)]
    hw //= 2
    c = 16
    for i, (k, exp, c_out, se, stride) in enumerate(_MBV3_SMALL):
        if exp != c:
            out.append(conv(f"b{i}/expand", hw, hw, c, exp, 1))
        out.append(dwconv(f"b{i}/dw", hw, hw, exp, k, stride=stride))
        hw //= stride
        if se:
            se_c = max(exp // 4, 8)
            out.extend([fc(f"b{i}/se_reduce", exp, se_c),
                        fc(f"b{i}/se_expand", se_c, exp)])
        out.append(conv(f"b{i}/project", hw, hw, exp, c_out, 1))
        c = c_out
    out.extend([conv("head/conv", hw, hw, c, 576, 1),
                fc("head/fc1", 576, 1024), fc("head/fc2", 1024, 1000)])
    return out


def resnet18(hw: int) -> list[Layer]:
    """conv1 + 8 basic blocks (2 convs each) + avgpool, residual sum, fc;
    downsample 1x1 convs run in the shadow of the main branch."""
    out = [conv("conv1", hw, hw, 3, 64, 7, stride=2)]
    hw //= 4                                  # stride 2, then maxpool
    c = 64
    for si, (width, first) in enumerate(((64, 1), (128, 2), (256, 2),
                                         (512, 2))):
        for bi in range(2):
            stride = first if bi == 0 else 1
            out.append(conv(f"s{si}b{bi}/conv1", hw, hw, c, width, 3,
                            stride=stride))
            hw //= stride
            out.append(conv(f"s{si}b{bi}/conv2", hw, hw, width, width, 3))
            c = width
    out.extend([pool("avgpool", hw, hw, c, hw, stride=hw),
                eltwise("residual_sum", 1, 1, c), fc("fc", 512, 1000)])
    return out


def mobilevit_xxs(hw: int) -> list[Layer]:
    """conv stem, MV2 blocks, three MobileViT blocks of transformer
    depth 2/4/3 (d = 64/80/96, MLP 2x), head."""
    out = [conv("stem", hw, hw, 3, 16, 3, stride=2)]
    hw //= 2

    def mv2(name, h, c_in, c_out, stride):
        e = 2 * c_in
        out.extend([conv(f"{name}/expand", h, h, c_in, e, 1),
                    dwconv(f"{name}/dw", h, h, e, 3, stride=stride),
                    conv(f"{name}/project", h // stride, h // stride, e,
                         c_out, 1)])
        return c_out

    def mvit(name, h, c_in, d, depth, patch=2):
        tokens = (h // patch) * (h // patch) * patch * patch // 4
        out.extend([conv(f"{name}/conv3x3", h, h, c_in, c_in, 3),
                    conv(f"{name}/conv1x1_in", h, h, c_in, d, 1),
                    eltwise(f"{name}/unfold", h, h, d)])
        for li in range(depth):
            out.extend([attention(f"{name}/tf{li}/attn", tokens, d, 4),
                        conv(f"{name}/tf{li}/ffn1", tokens, 1, d, 2 * d, 1),
                        conv(f"{name}/tf{li}/ffn2", tokens, 1, 2 * d, d, 1)])
        out.extend([eltwise(f"{name}/fold", h, h, d),
                    conv(f"{name}/conv1x1_out", h, h, d, c_in, 1),
                    conv(f"{name}/fusion", h, h, 2 * c_in, c_in, 3)])
        return c_in

    c = mv2("mv2_0", hw, 16, 16, 1)
    c = mv2("mv2_1", hw, c, 24, 2)
    hw //= 2
    c = mv2("mv2_2", hw, c, 24, 1)
    c = mv2("mv2_3", hw, c, 24, 1)
    c = mv2("mv2_4", hw, c, 48, 2)
    hw //= 2
    c = mvit("mvit_0", hw, c, 64, 2)
    c = mv2("mv2_5", hw, c, 64, 2)
    hw //= 2
    c = mvit("mvit_1", hw, c, 80, 4)
    c = mv2("mv2_6", hw, c, 80, 2)
    hw //= 2
    c = mvit("mvit_2", hw, c, 96, 3)
    out.extend([conv("head/conv1x1", hw, hw, c, 320, 1),
                pool("head/pool", hw, hw, 320, hw, stride=hw),
                fc("head/fc", 320, 1000)])
    return out


#: network name → (layer-list function, published input resolution)
NETWORKS = {
    "squeezenet1.1": (squeezenet_1_1, 224),
    "mobilenetv3-small": (mobilenetv3_small, 224),
    "resnet18": (resnet18, 224),
    "mobilevit-xxs": (mobilevit_xxs, 256),
}


def network(name: str, input_hw: int | None = None) -> list[Layer]:
    layers_of, published = NETWORKS[name]
    return layers_of(input_hw or published)
