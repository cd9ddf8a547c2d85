"""Model layer: the linear learners the reference substrate was built to feed.

dmlc-core itself contains no models, but its Row::SDot (data.h:146-161) and
RowBlock design exist to serve linear learners (XGBoost's linear booster,
wormhole's linear solvers). The flagship end-to-end slice here is therefore
a jit/pjit logistic-regression / linear-regression SGD learner over the
device pipeline — the SURVEY.md §7 "minimum slice" model — plus the
second-order factorization machine (models/fm.py) and the field-aware
one that reads the libfm format's field column (models/ffm.py).
"""

from dmlc_tpu.models.als import AlsLearner, AlsParams
from dmlc_tpu.models.ffm import FFMLearner, FFMParams
from dmlc_tpu.models.fm import FMLearner, FMParams
from dmlc_tpu.models.linear import LinearLearner, LinearParams

__all__ = ["AlsLearner", "AlsParams", "FFMLearner", "FFMParams", "FMLearner",
           "FMParams", "LinearLearner", "LinearParams"]
