"""A small trace of the shape a v5e's has where the model parts are read
(seen on the chip, PR 36): a device plane whose ``XLA Modules`` events
are program runs (``jit_serving_step(<id>)``) and whose ``XLA Ops``
events are named by HLO text, with the framework name in the ``tf_op``
stat of each op's METADATA entry, and a host plane with ``bench.window``.
Made with ``ProfileData.text_proto_to_serialized_xspace``, which the
code under test does not share. Times in ns:

    window            [500, 60000)
    step run 0        [100, 900)      starts before the window: cut
    prefill run       [1000, 11000)   while.5 [1000, 8000) holding
                                      fusion.1 [1500, 3500) attn_in and the
                                      kernel flash.2 [4000, 7000) attn;
                                      fusion.9 [8000, 9000) head;
                                      copy.3 [9000, 9500), no part
    step runs 1, 2    [20000, 26000), [30000, 36000): fusion.20 2000 ffn,
                                      the kernel paged.1 3000 layers; run 1
                                      also fusion.30 500, ffn backward
    copy.3            [40000, 40400)  in no program
    train run         [50000, 58000)  fusion.40 2000 ffn, flash_fwd.1 1000
                                      attn, fusion.41 3000 ffn backward,
                                      flash_bwd.1 1000 attn backward,
                                      fusion.42 500 optimizer, fusion.43
                                      500 loss
"""

import re

KERNEL = 'custom-call(%x), custom_call_target=\\"tpu_custom_call\\"'
# metadata id: (HLO text, display name, tf_op or None); the result type
# between " = " and the opcode is the op's shape_with_layout stat
META = {
    1: ("jit_serving_prefill(11)", "", None),
    2: ("jit_serving_step(22)", "", None),
    3: ("jit_train_step(33)", "", None),
    4: ("%while.5 = (s32[], bf16[4,8]{1,0}) while(%t), body=%b", "while.5",
        None),
    5: ("%fusion.1 = bf16[4,8]{1,0} fusion(%p), kind=kOutput", "fusion.1",
        "jit(serving_prefill)/while/body/closed_call/part.attn_in/"
        "dot_general:"),
    6: (f"%flash.2 = bf16[4,8]{{1,0}} {KERNEL}", "flash.2",
        "jit(serving_prefill)/while/body/closed_call/part.attn/flash/"
        "pallas_call:"),
    7: ("%fusion.9 = f32[4]{0} fusion(%p), kind=kLoop", "fusion.9",
        "jit(serving_prefill)/part.head/dot_general:"),
    8: ("%copy.3 = bf16[4,8]{0,1} copy(%p)", "copy.3", None),
    9: ("%fusion.20 = bf16[4,8]{1,0} fusion(%p), kind=kOutput", "fusion.20",
        "jit(serving_step)/part.ffn/dot_general:"),
    10: (f"%paged.1 = bf16[4,8]{{1,0}} {KERNEL}", "paged.1",
         "jit(serving_step)/part.layers/paged/pallas_call:"),
    11: ("%fusion.30 = bf16[4,8]{1,0} fusion(%p), kind=kLoop", "fusion.30",
         "jit(serving_step)/transpose(jvp(part.ffn))/mul:"),
    12: ("%fusion.40 = bf16[4,8]{1,0} fusion(%p), kind=kOutput", "fusion.40",
         "jit(train_step)/jvp(part.ffn)/dot_general:"),
    13: (f"%flash_fwd.1 = bf16[4,8]{{1,0}} {KERNEL}", "flash_fwd.1",
         "jit(train_step)/jvp(part.attn)/flash_fwd/pallas_call:"),
    14: ("%fusion.41 = bf16[4,8]{1,0} fusion(%p), kind=kOutput", "fusion.41",
         "jit(train_step)/transpose(jvp(part.ffn))/dot_general:"),
    15: (f"%flash_bwd.1 = bf16[4,8]{{1,0}} {KERNEL}", "flash_bwd.1",
         "jit(train_step)/transpose(jvp(part.attn))/flash_bwd/pallas_call:"),
    16: ("%fusion.42 = f32[4,8]{1,0} fusion(%p), kind=kLoop", "fusion.42",
         "jit(train_step)/part.optimizer/mul:"),
    17: ("%fusion.43 = f32[]{} fusion(%p), kind=kLoop", "fusion.43",
         "jit(train_step)/part.loss/reduce_sum:"),
}
RUNS = [(2, 100, 900), (1, 1000, 11000), (2, 20000, 26000),
        (2, 30000, 36000), (3, 50000, 58000)]
OPS = [(9, 100, 700),
       (4, 1000, 8000), (5, 1500, 3500), (6, 4000, 7000), (7, 8000, 9000),
       (8, 9000, 9500),
       (9, 20000, 22000), (10, 22000, 25000), (11, 25000, 25500),
       (9, 30000, 32000), (10, 32000, 35000),
       (8, 40000, 40400),
       (12, 50000, 52000), (13, 52000, 53000), (14, 53000, 56000),
       (15, 56000, 57000), (16, 57000, 57500), (17, 57500, 58000)]


def fixture_text() -> str:
    def events(rows):
        return "".join(
            f"    events {{ metadata_id: {m} offset_ps: {s * 1000} "
            f"duration_ps: {(e - s) * 1000} }}\n" for m, s, e in rows)

    meta = ""
    for i, (name, display, tf_op) in META.items():
        shape = re.split(r" [\w-]+\(", name.partition(" = ")[2], 1)[0]
        stats = (f' stats {{ metadata_id: 2 str_value: "{shape}" }}'
                 if display else "")
        if tf_op:
            stats += f' stats {{ metadata_id: 1 str_value: "{tf_op}" }}'
        shown = f' display_name: "{display}"' if display else ""
        meta += (f'  event_metadata {{ key: {i} value {{ id: {i} '
                 f'name: "{name}"{shown}{stats} }} }}\n')
    return (
        'planes { name: "/device:TPU:0"\n'
        '  lines { name: "XLA Modules" timestamp_ns: 0\n' + events(RUNS)
        + '  }\n  lines { name: "XLA Ops" timestamp_ns: 0\n' + events(OPS)
        + "  }\n" + meta
        + '  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }\n'
        '  stat_metadata { key: 2 value { id: 2 name: "shape_with_layout" } '
        "}\n}\n"
        'planes { name: "/host:CPU"\n'
        '  lines { name: "bench" timestamp_ns: 0\n'
        "    events { metadata_id: 1 offset_ps: 500000 "
        "duration_ps: 59500000 }\n  }\n"
        '  event_metadata { key: 1 value { id: 1 name: "bench.window" } }\n'
        "}\n")




def write(path: str) -> str:
    from jax.profiler import ProfileData
    with open(path, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(fixture_text()))
    return path
