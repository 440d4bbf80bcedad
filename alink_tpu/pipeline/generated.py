"""Reflective closure of the reference's pipeline class surface.

Capability parity with the generated pipeline layer (reference:
core/src/main/java/com/alibaba/alink/pipeline/**/*.java — ~326 thin
Trainer/Transformer/Model wrappers over the batch ops, produced there by
codegen). Here the same surface is produced at import time from four spec
tables (reference pipeline name -> our operator names): an Estimator gets
the train/predict pair, a Model the predict op, a Transformer its map op,
and a Recommender its recomm op. Params mirror the underlying ops'
ParamInfos so the fluent setters work identically, and every class lands
in STAGE_REGISTRY for pipeline-model persistence.

Hand-written stages in estimators.py take precedence; only names absent
there are generated.
"""

from __future__ import annotations

from typing import Dict, Type

from ..common.params import ParamInfo
from ..operator import batch as _B
from .base import EstimatorBase, ModelBase, TransformerBase

__all__ = []  # filled by the factories below


# -- spec tables (reference pipeline name -> operator class names) -----------

ESTIMATORS: Dict[str, tuple] = {
    'AutoCross': ('AutoCrossTrainBatchOp', 'AutoCrossPredictBatchOp', 'AutoCrossModel'),
    'AutoCrossAlgo': ('AutoCrossTrainBatchOp', 'AutoCrossPredictBatchOp', 'AutoCrossAlgoModel'),
    'BertTextClassifier': ('BertTextClassifierTrainBatchOp', 'BertTextClassifierPredictBatchOp', 'BertTextClassifierModel'),
    'BertTextPairClassifier': ('BertTextPairClassifierTrainBatchOp', 'BertTextPairClassifierPredictBatchOp', 'BertTextPairClassifierModel'),
    'BertTextPairRegressor': ('BertTextPairRegressorTrainBatchOp', 'BertTextPairRegressorPredictBatchOp', 'BertTextPairRegressorModel'),
    'BertTextRegressor': ('BertTextRegressorTrainBatchOp', 'BertTextRegressorPredictBatchOp', 'BertTextRegressorModel'),
    'C45': ('C45TrainBatchOp', 'C45PredictBatchOp', 'C45Model'),
    'C45Encoder': ('C45EncoderTrainBatchOp', 'TreeModelEncoderBatchOp', 'C45EncoderModel'),
    'Cart': ('CartTrainBatchOp', 'CartPredictBatchOp', 'CartModel'),
    'CartEncoder': ('CartEncoderTrainBatchOp', 'TreeModelEncoderBatchOp', 'CartEncoderModel'),
    'CartReg': ('CartRegTrainBatchOp', 'CartRegPredictBatchOp', 'CartRegModel'),
    'CartRegEncoder': ('CartRegEncoderTrainBatchOp', 'TreeModelEncoderBatchOp', 'CartRegEncoderModel'),
    'CrossCandidateSelector': ('CrossCandidateSelectorTrainBatchOp', 'CrossCandidateSelectorPredictBatchOp', 'CrossCandidateSelectorModel'),
    'CrossFeature': ('CrossFeatureTrainBatchOp', 'CrossFeaturePredictBatchOp', 'CrossFeatureModel'),
    'DecisionTreeEncoder': ('DecisionTreeEncoderTrainBatchOp', 'TreeModelEncoderBatchOp', 'DecisionTreeEncoderModel'),
    'DecisionTreeRegEncoder': ('DecisionTreeRegEncoderTrainBatchOp', 'TreeModelEncoderBatchOp', 'DecisionTreeRegEncoderModel'),
    'DecisionTreeRegressor': ('DecisionTreeRegTrainBatchOp', 'DecisionTreeRegPredictBatchOp', 'DecisionTreeRegressionModel'),
    'DocCountVectorizer': ('DocCountVectorizerTrainBatchOp', 'DocCountVectorizerPredictBatchOp', 'DocCountVectorizerModel'),
    'DocHashCountVectorizer': ('DocHashCountVectorizerTrainBatchOp', 'DocHashCountVectorizerPredictBatchOp', 'DocHashCountVectorizerModel'),
    'EqualWidthDiscretizer': ('EqualWidthDiscretizerTrainBatchOp', 'EqualWidthDiscretizerPredictBatchOp', 'EqualWidthDiscretizerModel'),
    'ExclusiveFeatureBundle': ('ExclusiveFeatureBundleTrainBatchOp', 'ExclusiveFeatureBundlePredictBatchOp', 'ExclusiveFeatureBundleModel'),
    'GbdtEncoder': ('GbdtEncoderTrainBatchOp', 'GbdtEncoderPredictBatchOp', 'GbdtEncoderModel'),
    'GbdtRegEncoder': ('GbdtRegEncoderTrainBatchOp', 'TreeModelEncoderBatchOp', 'GbdtRegEncoderModel'),
    'GeoKMeans': ('GeoKMeansTrainBatchOp', 'GeoKMeansPredictBatchOp', 'GeoKMeansModel'),
    'IForestModelOutlier': ('IForestModelOutlierTrainBatchOp', 'IForestModelOutlierPredictBatchOp', 'IForestModelOutlierModel'),
    'Id3': ('Id3TrainBatchOp', 'Id3PredictBatchOp', 'Id3Model'),
    'Id3Encoder': ('Id3EncoderTrainBatchOp', 'TreeModelEncoderBatchOp', 'Id3EncoderModel'),
    'KModes': ('KModesTrainBatchOp', 'KModesPredictBatchOp', 'KModesModel'),
    'KerasSequentialClassifier': ('KerasSequentialClassifierTrainBatchOp', 'KerasSequentialClassifierPredictBatchOp', 'KerasSequentialClassifierModel'),
    'KerasSequentialRegressor': ('KerasSequentialRegressorTrainBatchOp', 'KerasSequentialRegressorPredictBatchOp', 'KerasSequentialRegressorModel'),
    'LassoRegression': ('LassoRegTrainBatchOp', 'LassoRegPredictBatchOp', 'LassoRegressionModel'),
    'LinearRegStepwise': ('LinearRegStepwiseTrainBatchOp', 'LinearRegStepwisePredictBatchOp', 'LinearRegStepwiseModel'),
    'MaxAbsScaler': ('MaxAbsScalerTrainBatchOp', 'MaxAbsScalerPredictBatchOp', 'MaxAbsScalerModel'),
    'MultiHotEncoder': ('MultiHotTrainBatchOp', 'MultiHotPredictBatchOp', 'MultiHotEncoderModel'),
    'MultiStringIndexer': ('MultiStringIndexerTrainBatchOp', 'MultiStringIndexerPredictBatchOp', 'MultiStringIndexerModel'),
    'NaiveBayesTextClassifier': ('NaiveBayesTextTrainBatchOp', 'NaiveBayesTextPredictBatchOp', 'NaiveBayesTextModel'),
    'OcsvmModelOutlier': ('OcsvmModelOutlierTrainBatchOp', 'OcsvmModelOutlierPredictBatchOp', 'OcsvmModelOutlierModel'),
    'OneVsRest': ('OneVsRestTrainBatchOp', 'OneVsRestPredictBatchOp', 'OneVsRestModel'),
    'RandomForestEncoder': ('RandomForestEncoderTrainBatchOp', 'TreeModelEncoderBatchOp', 'RandomForestEncoderModel'),
    'RandomForestRegEncoder': ('RandomForestRegEncoderTrainBatchOp', 'TreeModelEncoderBatchOp', 'RandomForestRegEncoderModel'),
    'RandomForestRegressor': ('RandomForestRegTrainBatchOp', 'RandomForestRegPredictBatchOp', 'RandomForestRegressionModel'),
    'RidgeRegression': ('RidgeRegTrainBatchOp', 'RidgeRegPredictBatchOp', 'RidgeRegressionModel'),
    'StringApproxNearestNeighbor': ('StringApproxNearestNeighborTrainBatchOp', 'StringApproxNearestNeighborPredictBatchOp', 'StringApproxNearestNeighborModel'),
    'StringNearestNeighbor': ('StringNearestNeighborTrainBatchOp', 'StringNearestNeighborPredictBatchOp', 'StringNearestNeighborModel'),
    'TF2TableModelTrainer': ('TF2TableModelTrainBatchOp', 'TFTableModelPredictBatchOp', 'TF2TableModelTrainerModel'),
    'TFTableModelTrainer': ('TFTableModelTrainBatchOp', 'TFTableModelPredictBatchOp', 'TFTableModelTrainerModel'),
    'TargetEncoder': ('TargetEncoderTrainBatchOp', 'TargetEncoderPredictBatchOp', 'TargetEncoderModel'),
    'TextApproxNearestNeighbor': ('TextApproxNearestNeighborTrainBatchOp', 'TextApproxNearestNeighborPredictBatchOp', 'TextApproxNearestNeighborModel'),
    'TextNearestNeighbor': ('TextNearestNeighborTrainBatchOp', 'TextNearestNeighborPredictBatchOp', 'TextNearestNeighborModel'),
    'VectorApproxNearestNeighbor': ('VectorApproxNearestNeighborTrainBatchOp', 'VectorApproxNearestNeighborPredictBatchOp', 'VectorApproxNearestNeighborModel'),
    'VectorImputer': ('VectorImputerTrainBatchOp', 'VectorImputerPredictBatchOp', 'VectorImputerModel'),
    'VectorMaxAbsScaler': ('VectorMaxAbsScalerTrainBatchOp', 'VectorMaxAbsScalerPredictBatchOp', 'VectorMaxAbsScalerModel'),
    'VectorMinMaxScaler': ('VectorMinMaxScalerTrainBatchOp', 'VectorMinMaxScalerPredictBatchOp', 'VectorMinMaxScalerModel'),
    'VectorNearestNeighbor': ('VectorNearestNeighborTrainBatchOp', 'VectorNearestNeighborPredictBatchOp', 'VectorNearestNeighborModel'),
    'VectorStandardScaler': ('VectorStandardScalerTrainBatchOp', 'VectorStandardScalerPredictBatchOp', 'VectorStandardScalerModel'),
    'XGBoostClassifier': ('XGBoostTrainBatchOp', 'XGBoostPredictBatchOp', 'XGBoostClassificationModel'),
    'XGBoostRegressor': ('XGBoostRegTrainBatchOp', 'XGBoostRegPredictBatchOp', 'XGBoostRegressionModel'),
}

MODELS: Dict[str, str] = {
    'LookupRecentDaysModel': 'LookupRecentDaysBatchOp',
    'IndexToString': 'IndexToStringPredictBatchOp',
    'TFTableModelPredictor': 'TFTableModelPredictBatchOp',
    'AggLookup': 'AggLookupBatchOp',
    'AutoCrossAlgoModel': 'AutoCrossPredictBatchOp',
    'AutoCrossModel': 'AutoCrossPredictBatchOp',
    'BertClassificationModel': 'BertTextClassifierPredictBatchOp',
    'BertRegressionModel': 'BertTextRegressorPredictBatchOp',
    'BertTextEmbedding': 'BertTextEmbeddingBatchOp',
    'C45EncoderModel': 'TreeModelEncoderBatchOp',
    'C45Model': 'C45PredictBatchOp',
    'CartEncoderModel': 'TreeModelEncoderBatchOp',
    'CartModel': 'CartPredictBatchOp',
    'CartRegEncoderModel': 'TreeModelEncoderBatchOp',
    'CartRegModel': 'CartRegPredictBatchOp',
    'CrossCandidateSelectorModel': 'CrossCandidateSelectorPredictBatchOp',
    'CrossFeatureModel': 'CrossFeaturePredictBatchOp',
    'DbscanModel': 'DbscanPredictBatchOp',
    'DecisionTreeClassificationModel': 'DecisionTreePredictBatchOp',
    'DecisionTreeEncoderModel': 'TreeModelEncoderBatchOp',
    'DecisionTreeRegEncoderModel': 'TreeModelEncoderBatchOp',
    'DecisionTreeRegressionModel': 'DecisionTreeRegPredictBatchOp',
    'DocCountVectorizerModel': 'DocCountVectorizerPredictBatchOp',
    'DocHashCountVectorizerModel': 'DocHashCountVectorizerPredictBatchOp',
    'EqualWidthDiscretizerModel': 'EqualWidthDiscretizerPredictBatchOp',
    'ExclusiveFeatureBundleModel': 'ExclusiveFeatureBundlePredictBatchOp',
    'FmClassificationModel': 'FmClassifierPredictBatchOp',
    'FmRegressionModel': 'FmRegressorPredictBatchOp',
    'GbdtClassificationModel': 'GbdtPredictBatchOp',
    'GbdtEncoderModel': 'GbdtEncoderPredictBatchOp',
    'GbdtRegEncoderModel': 'TreeModelEncoderBatchOp',
    'GbdtRegressionModel': 'GbdtRegPredictBatchOp',
    'GeneralizedLinearRegressionModel': 'GlmPredictBatchOp',
    'GeoKMeansModel': 'GeoKMeansPredictBatchOp',
    'GroupScoreModel': 'GroupScorecardPredictBatchOp',
    'IForestModelOutlierModel': 'IForestModelOutlierPredictBatchOp',
    'Id3EncoderModel': 'TreeModelEncoderBatchOp',
    'Id3Model': 'Id3PredictBatchOp',
    'KModesModel': 'KModesPredictBatchOp',
    'KerasSequentialClassificationModel': 'KerasSequentialClassifierPredictBatchOp',
    'KerasSequentialRegressionModel': 'KerasSequentialRegressorPredictBatchOp',
    'KnnClassificationModel': 'KnnPredictBatchOp',
    'LassoRegressionModel': 'LassoRegPredictBatchOp',
    'LinearRegStepwiseModel': 'LinearRegStepwisePredictBatchOp',
    'LinearRegressionModel': 'LinearRegPredictBatchOp',
    'LinearSvmModel': 'LinearSvmPredictBatchOp',
    'LinearSvrModel': 'LinearSvrPredictBatchOp',
    'LogisticRegressionModel': 'LogisticRegressionPredictBatchOp',
    'Lookup': 'LookupBatchOp',
    'MaxAbsScalerModel': 'MaxAbsScalerPredictBatchOp',
    'MultiHotEncoderModel': 'MultiHotPredictBatchOp',
    'MultiStringIndexerModel': 'MultiStringIndexerPredictBatchOp',
    'MultilayerPerceptronClassificationModel': 'MultilayerPerceptronPredictBatchOp',
    'NaiveBayesTextModel': 'NaiveBayesTextPredictBatchOp',
    'OcsvmModelOutlierModel': 'OcsvmModelOutlierPredictBatchOp',
    'OneVsRestModel': 'OneVsRestPredictBatchOp',
    'RandomForestClassificationModel': 'RandomForestPredictBatchOp',
    'RandomForestEncoderModel': 'TreeModelEncoderBatchOp',
    'RandomForestRegEncoderModel': 'TreeModelEncoderBatchOp',
    'RandomForestRegressionModel': 'RandomForestRegPredictBatchOp',
    'RecommendationRanking': 'RecommendationRankingBatchOp',
    'RidgeRegressionModel': 'RidgeRegPredictBatchOp',
    'ScoreModel': 'ScorecardPredictBatchOp',
    'ScorecardModel': 'ScorecardPredictBatchOp',
    'SimpleGroupScoreModel': 'GroupScorecardPredictBatchOp',
    'SoftmaxModel': 'SoftmaxPredictBatchOp',
    'StringApproxNearestNeighborModel': 'StringApproxNearestNeighborPredictBatchOp',
    'StringNearestNeighborModel': 'StringNearestNeighborPredictBatchOp',
    'TFTableModelClassificationModel': 'TFTableModelClassifierPredictBatchOp',
    'TFTableModelRegressionModel': 'TFTableModelRegressorPredictBatchOp',
    'TargetEncoderModel': 'TargetEncoderPredictBatchOp',
    'TextApproxNearestNeighborModel': 'TextApproxNearestNeighborPredictBatchOp',
    'TextNearestNeighborModel': 'TextNearestNeighborPredictBatchOp',
    'VectorApproxNearestNeighborModel': 'VectorApproxNearestNeighborPredictBatchOp',
    'VectorImputerModel': 'VectorImputerPredictBatchOp',
    'VectorMaxAbsScalerModel': 'VectorMaxAbsScalerPredictBatchOp',
    'VectorMinMaxScalerModel': 'VectorMinMaxScalerPredictBatchOp',
    'VectorNearestNeighborModel': 'VectorNearestNeighborPredictBatchOp',
    'VectorStandardScalerModel': 'VectorStandardScalerPredictBatchOp',
    'XGBoostClassificationModel': 'XGBoostPredictBatchOp',
    'XGBoostRegressionModel': 'XGBoostRegPredictBatchOp',
}

TRANSFORMERS: Dict[str, str] = {
    'Binarizer': 'BinarizerBatchOp',
    'Bucketizer': 'BucketizerBatchOp',
    'ColumnsToCsv': 'ColumnsToCsvBatchOp',
    'ColumnsToJson': 'ColumnsToJsonBatchOp',
    'ColumnsToKv': 'ColumnsToKvBatchOp',
    'ColumnsToVector': 'ColumnsToVectorBatchOp',
    'CsvToColumns': 'CsvToColumnsBatchOp',
    'CsvToJson': 'CsvToJsonBatchOp',
    'CsvToKv': 'CsvToKvBatchOp',
    'CsvToVector': 'CsvToVectorBatchOp',
    'DCT': 'DCTBatchOp',
    'Dbscan': 'DbscanBatchOp',
    'ExtractMfccFeature': 'ExtractMfccFeatureBatchOp',
    'HashCrossFeature': 'HashCrossFeatureBatchOp',
    'IForestOutlier4GroupedData': 'IForestOutlier4GroupedDataBatchOp',
    'JsonToColumns': 'JsonToColumnsBatchOp',
    'JsonToCsv': 'JsonToCsvBatchOp',
    'JsonToKv': 'JsonToKvBatchOp',
    'JsonToVector': 'JsonToVectorBatchOp',
    'JsonValue': 'JsonValueBatchOp',
    'KvToColumns': 'KvToColumnsBatchOp',
    'KvToCsv': 'KvToCsvBatchOp',
    'KvToJson': 'KvToJsonBatchOp',
    'KvToVector': 'KvToVectorBatchOp',
    'LookupHBase': 'LookupHBaseBatchOp',
    'CausalLMGenerator': 'CausalLMGenerateBatchOp',
    'LookupRedisRow': 'LookupRedisRowBatchOp',
    'LookupRedisString': 'LookupRedisStringBatchOp',
    'NGram': 'NGramBatchOp',
    'OnnxModelPredictor': 'OnnxModelPredictBatchOp',
    'ReadAudioToTensor': 'ReadAudioToTensorBatchOp',
    'ReadImageToTensor': 'ReadImageToTensorBatchOp',
    'RegexTokenizer': 'RegexTokenizerBatchOp',
    'Segment': 'SegmentBatchOp',
    'StopWordsRemover': 'StopWordsRemoverBatchOp',
    'StringSimilarityPairwise': 'StringSimilarityPairwiseBatchOp',
    'TFSavedModelPredictor': 'TFSavedModelPredictBatchOp',
    'TensorReshape': 'TensorReshapeBatchOp',
    'TensorToVector': 'TensorToVectorBatchOp',
    'TextSimilarityPairwise': 'TextSimilarityPairwiseBatchOp',
    'ToMTable': 'ToMTableBatchOp',
    'ToTensor': 'ToTensorBatchOp',
    'ToVector': 'ToVectorBatchOp',
    'Tokenizer': 'TokenizerBatchOp',
    'TorchModelPredictor': 'TorchModelPredictBatchOp',
    'VectorBiFunction': 'VectorBiFunctionBatchOp',
    'VectorElementwiseProduct': 'VectorElementwiseProductBatchOp',
    'VectorFunction': 'VectorFunctionBatchOp',
    'VectorInteraction': 'VectorInteractionBatchOp',
    'VectorNormalizer': 'VectorNormalizeBatchOp',
    'VectorPolynomialExpand': 'VectorPolynomialExpandBatchOp',
    'VectorSizeHint': 'VectorSizeHintBatchOp',
    'VectorSlicer': 'VectorSliceBatchOp',
    'VectorToColumns': 'VectorToColumnsBatchOp',
    'VectorToCsv': 'VectorToCsvBatchOp',
    'VectorToJson': 'VectorToJsonBatchOp',
    'VectorToKv': 'VectorToKvBatchOp',
    'VectorToTensor': 'VectorToTensorBatchOp',
    'WriteTensorToImage': 'WriteTensorToImageBatchOp',
}

RECOMMENDERS: Dict[str, str] = {
    'AlsItemsPerUserRecommender': 'AlsItemsPerUserRecommBatchOp',
    'AlsRateRecommender': 'AlsRateRecommBatchOp',
    'AlsSimilarItemsRecommender': 'AlsSimilarItemsRecommBatchOp',
    'AlsSimilarUsersRecommender': 'AlsSimilarUsersRecommBatchOp',
    'AlsUsersPerItemRecommender': 'AlsUsersPerItemRecommBatchOp',
    'FmItemsPerUserRecommender': 'FmItemsPerUserRecommBatchOp',
    'FmRateRecommender': 'FmRateRecommBatchOp',
    'FmUsersPerItemRecommender': 'FmUsersPerItemRecommBatchOp',
    'ItemCfItemsPerUserRecommender': 'ItemCfItemsPerUserRecommBatchOp',
    'ItemCfRateRecommender': 'ItemCfRateRecommBatchOp',
    'ItemCfSimilarItemsRecommender': 'ItemCfSimilarItemsRecommBatchOp',
    'ItemCfUsersPerItemRecommender': 'ItemCfUsersPerItemRecommBatchOp',
    'SwingSimilarItemsRecommender': 'SwingSimilarItemsRecommBatchOp',
    'UserCfItemsPerUserRecommender': 'UserCfItemsPerUserRecommBatchOp',
    'UserCfRateRecommender': 'UserCfRateRecommBatchOp',
    'UserCfSimilarUsersRecommender': 'UserCfSimilarUsersRecommBatchOp',
    'UserCfUsersPerItemRecommender': 'UserCfUsersPerItemRecommBatchOp',
    'VecDotItemsPerUserRecommender': 'VecDotItemsPerUserRecommBatchOp',
}


# serving-only param names: when train and predict ops both define one, the
# predict op's definition (default/validator) is the one the estimator's
# transform path actually honors, so mirror that — not first-wins
_SERVING_PARAM_NAMES = frozenset(
    {"predictionCol", "predictionDetailCol", "reservedCols"})


def _mirror_params(*op_classes) -> Dict[str, ParamInfo]:
    out: Dict[str, ParamInfo] = {}
    for cls in op_classes:
        mine: Dict[str, ParamInfo] = {}
        for klass in cls.__mro__:
            for k, v in vars(klass).items():
                if isinstance(v, ParamInfo) and k not in mine:
                    mine[k] = v  # most-derived definition wins within a class
        for k, v in mine.items():
            if k not in out or (
                out[k] is not v and v.name in _SERVING_PARAM_NAMES
            ):
                out[k] = v
    return out


def _doc(ref_kind: str, name: str) -> str:
    return (f"Generated pipeline stage (reference: pipeline/**/{name}.java"
            f" — {ref_kind}).")


class BaseRecommender(ModelBase):
    """Base of the generated recommenders (reference:
    pipeline/recommendation/BaseRecommender.java): holds the trained
    recommendation model, transform links the bound recomm op."""


def _make_model(name: str, predict_op: Type, base=ModelBase) -> type:
    cls = type(name, (base,), {
        "__doc__": _doc("ModelBase subclass", name),
        "__module__": __name__,
        "_predict_op_cls": predict_op,
        **_mirror_params(predict_op),
    })
    return cls


def _build():
    g = globals()
    # hand-written stages (estimators.py + bases) take precedence: never
    # generate a class whose name they already define, or the generated
    # twin would shadow them in the package namespace and STAGE_REGISTRY
    from . import estimators as _hand
    from .base import STAGE_REGISTRY as _reg

    existing = {n for n in vars(_hand) if not n.startswith("_")}
    existing |= set(_reg)

    def taken(name):
        return name in g or name in existing

    def put(cls):
        g[cls.__name__] = cls
        __all__.append(cls.__name__)

    for name, predict_name in MODELS.items():
        if taken(name):
            continue
        put(_make_model(name, getattr(_B, predict_name)))

    for name, (train_name, predict_name, model_name) in ESTIMATORS.items():
        train_op = getattr(_B, train_name)
        predict_op = getattr(_B, predict_name)
        if not taken(model_name):
            put(_make_model(model_name, predict_op))
        model_cls = g.get(model_name) or _reg.get(model_name) \
            or getattr(_hand, model_name, None)
        if taken(name):
            continue
        put(type(name, (EstimatorBase,), {
            "__doc__": _doc(f"Trainer over {train_name}", name),
            "__module__": __name__,
            "_train_op_cls": train_op,
            "_model_cls": model_cls,
            **_mirror_params(train_op, predict_op),
        }))

    for name, op_name in TRANSFORMERS.items():
        if taken(name):
            continue
        op = getattr(_B, op_name)
        put(type(name, (TransformerBase,), {
            "__doc__": _doc(f"MapTransformer over {op_name}", name),
            "__module__": __name__,
            "_map_op_cls": op,
            **_mirror_params(op),
        }))

    for name, op_name in RECOMMENDERS.items():
        if taken(name):
            continue
        put(_make_model(name, getattr(_B, op_name), base=BaseRecommender))


_build()
__all__.append("BaseRecommender")
