from paddlebox_tpu.models.layers import mlp_init, mlp_apply
from paddlebox_tpu.models.ctr_dnn import CtrDnn
from paddlebox_tpu.models.deepfm import DeepFM
from paddlebox_tpu.models.wide_deep import WideDeep
from paddlebox_tpu.models.dlrm import DLRM
from paddlebox_tpu.models.mmoe import MMoE
from paddlebox_tpu.models.esmm import ESMM
from paddlebox_tpu.models.join_pv import JoinPvDnn
from paddlebox_tpu.models.nn_cross import CtrDnnExpand
from paddlebox_tpu.models.aux_input import CtrDnnAux
from paddlebox_tpu.models.bst import BstSeqCtr
from paddlebox_tpu.models.wide_tower import EpMMoE, TpDeepFM
from paddlebox_tpu.models.afmoe import AfMoE
from paddlebox_tpu.models.granite_hybrid import GraniteHybrid
from paddlebox_tpu.models.nemotron_h import NemotronH

MODEL_ZOO = {
    "ctr_dnn": CtrDnn,
    "deepfm": DeepFM,
    "wide_deep": WideDeep,
    "dlrm": DLRM,
    "mmoe": MMoE,
    "esmm": ESMM,
    "join_pv_dnn": JoinPvDnn,
    "ctr_dnn_expand": CtrDnnExpand,
    "ctr_dnn_aux": CtrDnnAux,
    "bst_seq_ctr": BstSeqCtr,
    "tp_deepfm": TpDeepFM,
    "ep_mmoe": EpMMoE,
    "afmoe": AfMoE,
    "granite_hybrid": GraniteHybrid,
    "nemotron_h": NemotronH,
}

__all__ = ["mlp_init", "mlp_apply", "CtrDnn", "DeepFM", "WideDeep", "DLRM",
           "MMoE", "ESMM", "JoinPvDnn", "CtrDnnExpand",
           "CtrDnnAux", "BstSeqCtr", "TpDeepFM", "EpMMoE", "AfMoE",
           "GraniteHybrid", "NemotronH", "MODEL_ZOO"]
