"""On-device (XLA/Pallas) data ops.

Reference parity: the decode half of CompressedImageCodec + the normalize work
every training loop does on host in the reference stack (petastorm/codecs.py:92-101
decodes on CPU; torch/tf pipelines then normalize on device or host).  Here
uint8->float normalize runs ON-CHIP fused (BASELINE.json north star: "uint8->float
normalization happens on-chip"), keeping the host->device transfer at 1 byte/pixel
(4x less PCIe/DCN traffic than shipping float32).
"""

from petastorm_tpu.ops.augment import (crop_boxes, cutmix, mixup, random_crop,
                                       random_crop_flip, random_flip,
                                       random_resized_crop, resize_images)
from petastorm_tpu.ops.normalize import normalize_images
from petastorm_tpu.ops.ring_attention import (ring_attention,
                                              ring_attention_sharded)
from petastorm_tpu.ops.ulysses import (ulysses_attention,
                                       ulysses_attention_sharded)

__all__ = ["normalize_images", "ring_attention", "ring_attention_sharded",
           "ulysses_attention", "ulysses_attention_sharded",
           "random_crop", "random_flip", "random_crop_flip",
           "random_resized_crop", "crop_boxes", "resize_images", "mixup",
           "cutmix"]
