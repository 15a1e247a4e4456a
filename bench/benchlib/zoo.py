"""The DNN task sets of MAGMA (arXiv:2104.13997, Section VI-A1) as plain
layer tuples, for the benchmark's reference.

A layer is ``(kind, N, K, C, Y, X, R, S, stride)`` with ``kind`` one of
``conv``, ``dwconv`` and ``fc``: batch N, output channels K, input
channels C, output rows Y and columns X, kernel R x S.  An FC/GEMM of
``M x N_out x K_in`` is ``("fc", 1, N_out, K_in, M, 1, 1, 1, 1)``; an
attention block is a bag of FCs.  The lists follow the paper's task sets
with vision mini-batches of 16 images, language sequences of 128 tokens
and recommendation batches of 8.  This file is the yardstick's frozen
copy: the system under test keeps its own zoo, and the two must agree.
"""
from __future__ import annotations

VISION_N = 16
LANG_SEQ = 128
RECOM_B = 8


def conv(N, K, C, Y, X, R, S, stride=1):
    return ("conv", N, K, C, Y, X, R, S, stride)


def dwconv(N, C, Y, X, R, S, stride=1):
    return ("dwconv", N, 1, C, Y, X, R, S, stride)


def fc(M, n_out, k_in):
    return ("fc", 1, n_out, k_in, M, 1, 1, 1, 1)


def attention(seq, d, heads, d_ff=None):
    dh = d // heads
    out = [fc(seq, 3 * d, d), fc(seq * heads, seq, dh),
           fc(seq * heads, dh, seq), fc(seq, d, d)]
    if d_ff:
        out += [fc(seq, d_ff, d), fc(seq, d, d_ff)]
    return out


def resnet50():
    N = VISION_N
    out = [conv(N, 64, 3, 112, 112, 7, 7, 2)]
    for K, mid, Y, blocks in [(256, 64, 56, 3), (512, 128, 28, 4),
                              (1024, 256, 14, 6), (2048, 512, 7, 3)]:
        out += [conv(N, mid, K, Y, Y, 1, 1), conv(N, mid, mid, Y, Y, 3, 3),
                conv(N, K, mid, Y, Y, 1, 1)] * blocks
    return out + [fc(N, 1000, 2048)]


def mobilenetv2():
    N = VISION_N
    out = [conv(N, 32, 3, 112, 112, 3, 3, 2)]
    for cin, cout, e, Y, blocks in [
            (32, 16, 1, 112, 1), (16, 24, 6, 56, 2), (24, 32, 6, 28, 3),
            (32, 64, 6, 14, 4), (64, 96, 6, 14, 3), (96, 160, 6, 7, 3),
            (160, 320, 6, 7, 1)]:
        out += [conv(N, cin * e, cin, Y, Y, 1, 1),
                dwconv(N, cin * e, Y, Y, 3, 3),
                conv(N, cout, cin * e, Y, Y, 1, 1)] * blocks
    return out + [conv(N, 1280, 320, 7, 7, 1, 1), fc(N, 1000, 1280)]


def shufflenet():
    N = VISION_N
    out = [conv(N, 24, 3, 56, 56, 3, 3, 2)]
    for C, Y, blocks in [(116, 28, 4), (232, 14, 8), (464, 7, 4)]:
        h = C // 2
        out += [conv(N, h, h, Y, Y, 1, 1), dwconv(N, h, Y, Y, 3, 3),
                conv(N, h, h, Y, Y, 1, 1)] * blocks
    return out + [conv(N, 1024, 464, 7, 7, 1, 1), fc(N, 1000, 1024)]


def vgg16():
    N = VISION_N
    out = []
    for C, K, Y, blocks in [(3, 64, 224, 1), (64, 64, 224, 1),
                            (64, 128, 112, 2), (128, 256, 56, 3),
                            (256, 512, 28, 3), (512, 512, 14, 3)]:
        out += [conv(N, K, max(C, K // 2), Y, Y, 3, 3)] * blocks
    return out + [fc(N, 4096, 25088), fc(N, 4096, 4096), fc(N, 1000, 4096)]


def mnasnet():
    N = VISION_N
    out = [conv(N, 32, 3, 112, 112, 3, 3, 2)]
    for i, (cin, cout, e, Y, blocks) in enumerate(
            [(32, 24, 3, 56, 3), (24, 40, 3, 28, 3), (40, 80, 6, 14, 3),
             (80, 112, 6, 14, 2), (112, 160, 6, 7, 3)]):
        k = 5 if i % 2 else 3
        out += [conv(N, cin * e, cin, Y, Y, 1, 1),
                dwconv(N, cin * e, Y, Y, k, k),
                conv(N, cout, cin * e, Y, Y, 1, 1)] * blocks
    return out + [fc(N, 1000, 1280)]


def gpt2():
    return attention(LANG_SEQ, 768, 12, 3072) * 12


def mobilebert():
    return (attention(LANG_SEQ, 128, 4, 512)
            + [fc(LANG_SEQ, 512, 128), fc(LANG_SEQ, 128, 512)]) * 24


def transformerxl():
    return (attention(LANG_SEQ, 512, 8, 2048)
            + [fc(LANG_SEQ * 8, LANG_SEQ, 64)]) * 16


def bert_base():
    return attention(LANG_SEQ, 768, 12, 3072) * 12


def alphagozero():
    N = VISION_N
    return ([conv(N, 256, 17, 19, 19, 3, 3)]
            + [conv(N, 256, 256, 19, 19, 3, 3)] * 40
            + [conv(N, 2, 256, 19, 19, 1, 1), fc(N, 362, 2 * 19 * 19),
               conv(N, 1, 256, 19, 19, 1, 1), fc(N, 256, 19 * 19),
               fc(N, 1, 256)])


def deepspeech2():
    T = LANG_SEQ
    out = [conv(1, 32, 1, T, 41, 11, 41, 2), conv(1, 32, 32, T, 21, 11, 21)]
    d_in, d_h = 32 * 21, 800
    for i in range(5):
        for _ in ("fw", "bw"):
            out += [fc(T, 3 * d_h, d_in if i == 0 else 2 * d_h),
                    fc(T, 3 * d_h, d_h)]
    return out + [fc(T, 29, 2 * d_h)]


def fasterrcnn():
    N = VISION_N
    return resnet50()[:-1] + [
        conv(N, 512, 2048, 14, 14, 3, 3), conv(N, 18, 512, 14, 14, 1, 1),
        conv(N, 36, 512, 14, 14, 1, 1), fc(128, 1024, 7 * 7 * 256),
        fc(128, 1024, 1024), fc(128, 91, 1024), fc(128, 364, 1024)]


def transformer():
    return (attention(LANG_SEQ, 512, 8, 2048) * 6
            + (attention(LANG_SEQ, 512, 8, 2048)
               + attention(LANG_SEQ, 512, 8)) * 6)


def dlrm():
    B = RECOM_B
    return [fc(B, 512, 13), fc(B, 256, 512), fc(B, 64, 256),
            fc(B, 512, 512), fc(B, 256, 512), fc(B, 1, 256)]


def widedeep():
    B = RECOM_B
    return [fc(B, 1024, 512), fc(B, 512, 1024), fc(B, 256, 512),
            fc(B, 1, 1024), fc(B, 1, 256)]


def ncf():
    B = RECOM_B
    return [fc(B, 256, 128), fc(B, 128, 256), fc(B, 64, 128),
            fc(B, 64, 64), fc(B, 1, 128)]


def din():
    B = RECOM_B
    return [fc(B, 80, 144), fc(B, 40, 80), fc(B, 1, 40),
            fc(B, 200, 288), fc(B, 80, 200), fc(B, 2, 80)]


MODELS = {
    "resnet50": resnet50, "mobilenetv2": mobilenetv2,
    "shufflenet": shufflenet, "vgg16": vgg16, "mnasnet": mnasnet,
    "gpt2": gpt2, "mobilebert": mobilebert, "transformerxl": transformerxl,
    "bert_base": bert_base, "alphagozero": alphagozero,
    "deepspeech2": deepspeech2, "fasterrcnn": fasterrcnn,
    "transformer": transformer, "dlrm": dlrm, "widedeep": widedeep,
    "ncf": ncf, "din": din,
}

TASK_SETS = {
    "Vision": ["resnet50", "mobilenetv2", "shufflenet", "vgg16", "mnasnet"],
    "Lang": ["gpt2", "mobilebert", "transformerxl", "bert_base"],
    "Recom": ["dlrm", "widedeep", "ncf", "din"],
    "Mix": ["resnet50", "mobilenetv2", "shufflenet", "gpt2", "mobilebert",
            "transformerxl", "dlrm", "widedeep", "ncf"],
    "Heavy": ["alphagozero", "fasterrcnn", "resnet50"],
    "Light": ["deepspeech2", "ncf", "transformer"],
    "HeavyLight": ["alphagozero", "fasterrcnn", "resnet50", "deepspeech2",
                   "ncf", "transformer"],
}

#: MAGMA Table III: (count, PE-array height, dataflow, global scratchpad KB)
#: per kind of sub-accelerator; every array is h x 64 PEs at 200 MHz.
SETTINGS = {
    "S1": [(4, 32, "HB", 146)],
    "S2": [(3, 32, "HB", 146), (1, 32, "LB", 110)],
    "S3": [(8, 128, "HB", 580)],
    "S4": [(7, 128, "HB", 580), (1, 128, "LB", 434)],
    "S5": [(3, 128, "HB", 580), (1, 128, "LB", 434),
           (3, 64, "HB", 291), (1, 64, "LB", 218)],
    "S6": [(7, 128, "HB", 580), (1, 128, "LB", 434),
           (7, 64, "HB", 291), (1, 64, "LB", 218)],
}


def sub_accels(setting: str):
    """``[(pe_h, dataflow, sg_bytes)]`` of a Table III setting, in order."""
    return [(h, df, kb * 1024) for count, h, df, kb in SETTINGS[setting]
            for _ in range(count)]
