"""Model families (reference: GluonCV/GluonNLP recipes + example/, the
workloads named in BASELINE.md)."""
from .bert import (  # noqa: F401
    BERTModel, BERTEncoder, TransformerEncoderLayer, MultiHeadAttention,
    PositionwiseFFN, bert_base, bert_large, bert_sharding_rules,
    BERTPretrainingLoss,
)
from .transformer import (  # noqa: F401
    Transformer, TransformerDecoderLayer, transformer_base,
    beam_search_translate,
)
from .lm import TransformerLM, tiny_lm  # noqa: F401
from .ssd import (  # noqa: F401
    SSD, SSDMultiBoxLoss, MultiBoxTarget, MultiBoxDetection,
    generate_anchors, ssd_300_resnet18, ssd_lite,
)
from .yolo import (  # noqa: F401
    DarknetV3, darknet53, YOLOV3, YOLOV3Loss, yolo3_targets,
    yolo3_darknet53_voc, yolo3_darknet53_coco, yolo3_tiny,
)
from .deepseek import DeepSeekV32LM, tiny_v32  # noqa: F401
from .lfm2 import LFM2MoeLM, tiny_lfm2  # noqa: F401
from .keye import KeyeVL2LM, tiny_keye  # noqa: F401
from .solar import SolarOpen2LM, tiny_solar  # noqa: F401
