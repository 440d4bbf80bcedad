from ..sql import (
    DistinctOp,
    FilterOp,
    GroupByOp,
    IntersectOp,
    JoinOp,
    MinusOp,
    OrderByOp,
    RenameOp,
    SampleOp,
    SelectOp,
    UnionAllOp,
    UnionOp,
)
from .base import (
    AkSinkBatchOp,
    AkSourceBatchOp,
    BatchOperator,
    CsvSinkBatchOp,
    CsvSourceBatchOp,
    FirstNBatchOp,
    MemSourceBatchOp,
    NumSeqSourceBatchOp,
    RandomTableSourceBatchOp,
    ShuffleBatchOp,
    SplitBatchOp,
    TableSourceBatchOp,
)


# Reference-style names for the SQL sugar ops (reference: operator/batch/sql/*.java)
class SelectBatchOp(SelectOp, BatchOperator):
    pass


class WhereBatchOp(FilterOp, BatchOperator):
    pass


class FilterBatchOp(FilterOp, BatchOperator):
    pass


class DistinctBatchOp(DistinctOp, BatchOperator):
    pass


class OrderByBatchOp(OrderByOp, BatchOperator):
    pass


class GroupByBatchOp(GroupByOp, BatchOperator):
    pass


class UnionAllBatchOp(UnionAllOp, BatchOperator):
    pass


class UnionBatchOp(UnionOp, BatchOperator):
    pass


class IntersectBatchOp(IntersectOp, BatchOperator):
    pass


class MinusBatchOp(MinusOp, BatchOperator):
    pass


class JoinBatchOp(JoinOp, BatchOperator):
    pass


class SampleBatchOp(SampleOp, BatchOperator):
    pass


from .utils import (LinearModelTrainInfoBatchOp, MapBatchOp, ModelMapBatchOp,
                    ModelTrainOpMixin, TrainInfoBatchOp)
from .modelpredict import (
    OnnxModelPredictBatchOp,
    StableHloModelPredictBatchOp,
    TFSavedModelPredictBatchOp,
    TorchModelPredictBatchOp,
    export_stablehlo,
)
from .lm import CausalLMGenerateBatchOp, CausalLMTrainBatchOp
from .clustering import (
    GeoKMeansPredictBatchOp,
    GeoKMeansTrainBatchOp,
    KMeansModelInfoBatchOp,
    KMeansPredictBatchOp,
    KMeansTrainBatchOp,
)
from .clustering2 import (
    AgnesBatchOp,
    GroupDbscanBatchOp,
    GroupKMeansBatchOp,
    BisectingKMeansPredictBatchOp,
    BisectingKMeansTrainBatchOp,
    DbscanBatchOp,
    GmmPredictBatchOp,
    GmmTrainBatchOp,
    KModesPredictBatchOp,
    KModesTrainBatchOp,
    LdaPredictBatchOp,
    LdaTrainBatchOp,
    SomPredictBatchOp,
    SomTrainBatchOp,
)
from .linear import (
    LassoRegPredictBatchOp,
    LassoRegTrainBatchOp,
    LinearRegPredictBatchOp,
    LinearRegTrainBatchOp,
    LinearSvmPredictBatchOp,
    LinearSvmTrainBatchOp,
    LogisticRegressionPredictBatchOp,
    LogisticRegressionTrainBatchOp,
    RidgeRegPredictBatchOp,
    RidgeRegTrainBatchOp,
    LinearSvrPredictBatchOp,
    LinearSvrTrainBatchOp,
    SoftmaxPredictBatchOp,
    SoftmaxTrainBatchOp,
)
from .regression import (
    AftSurvivalRegPredictBatchOp,
    StepwiseLinearRegTrainBatchOp,
    AftSurvivalRegTrainBatchOp,
    GlmPredictBatchOp,
    GlmTrainBatchOp,
    IsotonicRegPredictBatchOp,
    IsotonicRegTrainBatchOp,
)
from .classification import (
    FmClassifierPredictBatchOp,
    FmClassifierTrainBatchOp,
    FmPredictBatchOp,
    FmRegressorPredictBatchOp,
    FmRegressorTrainBatchOp,
    KnnPredictBatchOp,
    KnnRegPredictBatchOp,
    KnnRegTrainBatchOp,
    KnnTrainBatchOp,
    MultilayerPerceptronPredictBatchOp,
    MultilayerPerceptronTrainBatchOp,
    NaiveBayesPredictBatchOp,
    NaiveBayesTrainBatchOp,
    OneVsRestPredictBatchOp,
    OneVsRestTrainBatchOp,
)
from .outlier import (
    CopodOutlier4GroupedDataBatchOp,
    EcodOutlier4GroupedDataBatchOp,
    HbosOutlier4GroupedDataBatchOp,
    KdeOutlier4GroupedDataBatchOp,
    LofOutlier4GroupedDataBatchOp,
    OcsvmOutlier4GroupedDataBatchOp,
    SosOutlier4GroupedDataBatchOp,
    BoxPlotOutlier4GroupedDataBatchOp,
    BoxPlotOutlierBatchOp,
    CopodOutlierBatchOp,
    EcodOutlierBatchOp,
    EsdOutlier4GroupedDataBatchOp,
    EsdOutlierBatchOp,
    EvalOutlierBatchOp,
    HbosOutlierBatchOp,
    IForestOutlier4GroupedDataBatchOp,
    IForestOutlierBatchOp,
    KdeOutlierBatchOp,
    KSigmaOutlier4GroupedDataBatchOp,
    KSigmaOutlierBatchOp,
    LofOutlierBatchOp,
    MadOutlier4GroupedDataBatchOp,
    MadOutlierBatchOp,
    OcsvmOutlierBatchOp,
    SosOutlierBatchOp,
    ShEsdOutlier4GroupedDataBatchOp,
    ShEsdOutlierBatchOp,
)
from .recommendation import (
    AlsItemsPerUserRecommBatchOp,
    AlsRateRecommBatchOp,
    AlsSimilarItemsRecommBatchOp,
    AlsTrainBatchOp,
    AlsUsersPerItemRecommBatchOp,
    ItemCfItemsPerUserRecommBatchOp,
    ItemCfRateRecommBatchOp,
    ItemCfSimilarItemsRecommBatchOp,
    ItemCfTrainBatchOp,
    SwingSimilarItemsRecommBatchOp,
    SwingTrainBatchOp,
    UserCfRateRecommBatchOp,
    UserCfTrainBatchOp,
)
from .evaluation import (
    EvalBinaryClassBatchOp,
    EvalClusterBatchOp,
    EvalMultiClassBatchOp,
    EvalMultiLabelBatchOp,
    EvalRankingBatchOp,
    EvalRegressionBatchOp,
)
from .feature import (
    MinMaxScalerPredictBatchOp,
    MinMaxScalerTrainBatchOp,
    StandardScalerPredictBatchOp,
    StandardScalerTrainBatchOp,
    VectorAssemblerBatchOp,
)
from .feature2 import (
    BinningPredictBatchOp,
    BinningTrainBatchOp,
    ChiSqSelectorBatchOp,
    ChiSqSelectorPredictBatchOp,
    EqualWidthDiscretizerPredictBatchOp,
    EqualWidthDiscretizerTrainBatchOp,
    FeatureHasherBatchOp,
    MaxAbsScalerPredictBatchOp,
    MaxAbsScalerTrainBatchOp,
    OneHotPredictBatchOp,
    OneHotTrainBatchOp,
    PcaPredictBatchOp,
    PcaTrainBatchOp,
    QuantileDiscretizerPredictBatchOp,
    QuantileDiscretizerTrainBatchOp,
    AutoCrossBatchOp,
    AutoCrossPredictBatchOp,
    DCTBatchOp,
)
from .dataproc import (
    ImputerPredictBatchOp,
    OverWindowBatchOp,
    RebalanceBatchOp,
    StratifiedSampleBatchOp,
    WeightSampleBatchOp,
    ImputerTrainBatchOp,
    JsonValueBatchOp,
    LookupBatchOp,
    StringIndexerPredictBatchOp,
    StringIndexerTrainBatchOp,
    TypeConvertBatchOp,
)
from .dl import (
    BertTextClassifierPredictBatchOp,
    BertTextClassifierTrainBatchOp,
    BertTextPairClassifierTrainBatchOp,
    BertTextRegressorPredictBatchOp,
    BertTextRegressorTrainBatchOp,
    KerasSequentialClassifierPredictBatchOp,
    KerasSequentialClassifierTrainBatchOp,
    KerasSequentialRegressorPredictBatchOp,
    KerasSequentialRegressorTrainBatchOp,
)
from .tree import (
    C45EncoderTrainBatchOp,
    C45PredictBatchOp,
    C45TrainBatchOp,
    CartEncoderTrainBatchOp,
    CartPredictBatchOp,
    CartRegEncoderTrainBatchOp,
    CartRegPredictBatchOp,
    CartRegTrainBatchOp,
    CartTrainBatchOp,
    DecisionTreeEncoderTrainBatchOp,
    DecisionTreePredictBatchOp,
    DecisionTreeRegEncoderTrainBatchOp,
    DecisionTreeRegPredictBatchOp,
    DecisionTreeRegTrainBatchOp,
    DecisionTreeTrainBatchOp,
    GbdtEncoderPredictBatchOp,
    GbdtEncoderTrainBatchOp,
    GbdtPredictBatchOp,
    GbdtRegEncoderTrainBatchOp,
    GbdtRegPredictBatchOp,
    GbdtRegTrainBatchOp,
    GbdtTrainBatchOp,
    Id3EncoderTrainBatchOp,
    Id3PredictBatchOp,
    Id3TrainBatchOp,
    RandomForestEncoderTrainBatchOp,
    RandomForestPredictBatchOp,
    RandomForestRegEncoderTrainBatchOp,
    RandomForestRegPredictBatchOp,
    RandomForestRegTrainBatchOp,
    RandomForestTrainBatchOp,
    TreeModelEncoderBatchOp,
)
from .statistics import (
    ChiSquareTestBatchOp,
    CorrelationBatchOp,
    CovarianceBatchOp,
    QuantileBatchOp,
    SummarizerBatchOp,
    VectorChiSquareTestBatchOp,
    VectorCorrelationBatchOp,
    VectorSummarizerBatchOp,
)
from .timeseries import (
    ArimaBatchOp,
    AutoArimaBatchOp,
    DeepARBatchOp,
    LSTNetBatchOp,
    ProphetBatchOp,
    TFTBatchOp,
    DifferenceBatchOp,
    EvalTimeSeriesBatchOp,
    GarchBatchOp,
    HoltWintersBatchOp,
    ShiftBatchOp,
)
from .graph import (
    MultiSourceShortestPathBatchOp,
    TreeDepthBatchOp,
    VertexNeighborSearchBatchOp,
    CommonNeighborsBatchOp,
    CommunityDetectionClusterBatchOp,
    ConnectedComponentsBatchOp,
    EdgeClusterCoefficientBatchOp,
    KCoreBatchOp,
    LouvainBatchOp,
    ModularityCalBatchOp,
    PageRankBatchOp,
    SingleSourceShortestPathBatchOp,
    TriangleListBatchOp,
    VertexClusterCoefficientBatchOp,
)
from .similarity import (
    StringNearestNeighborPredictBatchOp,
    StringNearestNeighborTrainBatchOp,
    StringSimilarityPairwiseBatchOp,
    TextNearestNeighborPredictBatchOp,
    TextNearestNeighborTrainBatchOp,
    TextSimilarityPairwiseBatchOp,
    VectorNearestNeighborPredictBatchOp,
    VectorNearestNeighborTrainBatchOp,
)
from .nlp import (
    DocCountVectorizerPredictBatchOp,
    DocHashCountVectorizerPredictBatchOp,
    DocHashCountVectorizerTrainBatchOp,
    DocCountVectorizerTrainBatchOp,
    DocWordCountBatchOp,
    KeywordsExtractionBatchOp,
    NGramBatchOp,
    SegmentBatchOp,
    StopWordsRemoverBatchOp,
    TfidfBatchOp,
    WordCountBatchOp,
)
from .associationrule import (
    AprioriBatchOp,
    FpGrowthBatchOp,
    PrefixSpanBatchOp,
)
from .sources import (
    LibSvmSinkBatchOp,
    LibSvmSourceBatchOp,
    ParquetSinkBatchOp,
    ParquetSourceBatchOp,
    TextSourceBatchOp,
    TFRecordSinkBatchOp,
    TFRecordSourceBatchOp,
    TsvSinkBatchOp,
    TsvSourceBatchOp,
)
from .finance import (
    PsiBatchOp,
    ScorecardPredictBatchOp,
    ScorecardTrainBatchOp,
)
from .vector import (
    ColumnsToVectorBatchOp,
    UdfBatchOp,
    UdtfBatchOp,
    VectorElementwiseProductBatchOp,
    VectorInteractionBatchOp,
    VectorNormalizeBatchOp,
    VectorSliceBatchOp,
    VectorToColumnsBatchOp,
)
from .media import (
    ExtractMfccFeatureBatchOp,
    ReadAudioToTensorBatchOp,
    ReadImageToTensorBatchOp,
)
from .insights import AutoDiscoveryBatchOp
from .xgboost import (
    XGBoostPredictBatchOp,
    XGBoostTrainBatchOp,
)
from ..sqlengine import (
    JdbcSinkBatchOp,
    JdbcSourceBatchOp,
    SqliteCatalog,
    SqlQueryBatchOp,
    sql_query,
)
from .connectors import (
    KvSinkBatchOp,
    LookupKvBatchOp,
)
from .recommendation import (
    DeepFmItemsPerUserRecommBatchOp,
    DeepFmRateRecommBatchOp,
    DeepFmRecommTrainBatchOp,
    FmItemsPerUserRecommBatchOp,
    FmRateRecommBatchOp,
    FmRecommTrainBatchOp,
    FmUsersPerItemRecommBatchOp,
    LeaveKObjectOutBatchOp,
    LeaveTopKObjectOutBatchOp,
)
from .tree import (
    GbdtEncoderBatchOp,
)
from .dataproc import (
    HugeMultiStringIndexerPredictBatchOp,
    HugeStringIndexerPredictBatchOp,
)
from .sources import (
    XlsSourceBatchOp,
)
from .finance import (
    GroupScorecardPredictBatchOp,
    GroupScorecardTrainBatchOp,
)
from .vector import (
    VectorImputerPredictBatchOp,
    VectorImputerTrainBatchOp,
    VectorMaxAbsScalerPredictBatchOp,
    VectorMaxAbsScalerTrainBatchOp,
    VectorMinMaxScalerPredictBatchOp,
    VectorMinMaxScalerTrainBatchOp,
    VectorStandardScalerPredictBatchOp,
    VectorStandardScalerTrainBatchOp,
)
from .utils2 import (
    AppendIdBatchOp,
    AppendModelStreamFileSinkBatchOp,
    DummySinkBatchOp,
    FlattenMTableBatchOp,
    GroupDataToMTableBatchOp,
    TextSinkBatchOp,
)
from . import modelinfo as _modelinfo
from .modelinfo import *  # noqa: F401,F403 — ModelInfo family
from . import format as _format
from .format import *  # noqa: F401,F403 — format conversion family
from .windowfe import (
    GenerateFeatureOfLatestBatchOp,
    GenerateFeatureOfLatestNDaysBatchOp,
    GenerateFeatureOfWindowBatchOp,
)
from .huge import (
    DeepWalkBatchOp,
    LineBatchOp,
    MetaPath2VecBatchOp,
    MetaPathWalkBatchOp,
    DeepWalkEmbeddingBatchOp,
    Node2VecEmbeddingBatchOp,
    Node2VecWalkBatchOp,
    RandomWalkBatchOp,
    Word2VecPredictBatchOp,
    Word2VecTrainBatchOp,
)
from .vector2 import (
    VectorBiFunctionBatchOp,
    VectorChiSqSelectorBatchOp,
    VectorFunctionBatchOp,
    VectorPolynomialExpandBatchOp,
    VectorSizeHintBatchOp,
)
from .tensorops import (
    MTableSerializeBatchOp,
    TensorReshapeBatchOp,
    TensorSerializeBatchOp,
    TensorToVectorBatchOp,
    ToMTableBatchOp,
    ToTensorBatchOp,
    ToVectorBatchOp,
    VectorSerializeBatchOp,
    VectorToTensorBatchOp,
)
from .feature3 import (
    BinarizerBatchOp,
    BucketizerBatchOp,
    ExclusiveFeatureBundlePredictBatchOp,
    ExclusiveFeatureBundleTrainBatchOp,
    IndexToStringPredictBatchOp,
    MultiHotPredictBatchOp,
    MultiHotTrainBatchOp,
    MultiStringIndexerPredictBatchOp,
    MultiStringIndexerTrainBatchOp,
    TargetEncoderPredictBatchOp,
    TargetEncoderTrainBatchOp,
)
from .relational2 import (
    AsBatchOp,
    DataSetWrapperBatchOp,
    FullOuterJoinBatchOp,
    IntersectAllBatchOp,
    LeftOuterJoinBatchOp,
    MinusAllBatchOp,
    PrintBatchOp,
    RandomVectorSourceBatchOp,
    RightOuterJoinBatchOp,
    SampleWithSizeBatchOp,
    StratifiedSampleWithSizeBatchOp,
)
from .udf2 import (
    BaseGroupPandasUdfBatchOp,
    BasePandasUdfBatchOp,
    BasePyScalarFnBatchOp,
    BasePyTableFnBatchOp,
    FlatMapBatchOp,
    FlatModelMapBatchOp,
    FlattenKObjectBatchOp,
    GroupPandasFileUdfBatchOp,
    GroupPandasUdfBatchOp,
    GroupRBatchOp,
    PandasUdfBatchOp,
    PandasUdfFileBatchOp,
    PyFileScalarFnBatchOp,
    PyFileTableFnBatchOp,
    PyScalarFnBatchOp,
    PyTableFnBatchOp,
    RUdfBatchOp,
    UDFBatchOp,
    UDTFBatchOp,
)
from .nlp import (
    RegexTokenizerBatchOp,
    TokenizerBatchOp,
)
from .huge import RandomWalkBatchOp
from .recommendation2 import (
    AlsForHotPointTrainBatchOp,
    AlsImplicitForHotPointTrainBatchOp,
    AlsImplicitTrainBatchOp,
    AlsSimilarUsersRecommBatchOp,
    FmRecommBinaryImplicitTrainBatchOp,
    ItemCfUsersPerItemRecommBatchOp,
    MfAlsBatchOp,
    MfAlsForHotPointBatchOp,
    NegativeItemSamplingBatchOp,
    RankingListBatchOp,
    RecommendationRankingBatchOp,
    SwingRecommBatchOp,
    UserCfItemsPerUserRecommBatchOp,
    UserCfSimilarUsersRecommBatchOp,
    UserCfUsersPerItemRecommBatchOp,
    VecDotItemsPerUserRecommBatchOp,
    VecDotModelGeneratorBatchOp,
)
from .outlier import (
    CooksDistanceOutlierBatchOp,
    DbscanModelOutlierPredictBatchOp,
    DbscanOutlier4GroupedDataBatchOp,
    DbscanOutlierBatchOp,
    DbscanPredictBatchOp,
    DynamicTimeWarpOutlierBatchOp,
    GroupDbscanModelBatchOp,
    IForestModelOutlierPredictBatchOp,
    IForestModelOutlierTrainBatchOp,
    OcsvmModelOutlierPredictBatchOp,
    OcsvmModelOutlierTrainBatchOp,
    SHEsdOutlierBatchOp,
)
from .timeseries2 import (
    AutoGarchBatchOp,
    DeepARPredictBatchOp,
    DeepARTrainBatchOp,
    LSTNetPredictBatchOp,
    LSTNetTrainBatchOp,
    LookupRecentDaysBatchOp,
    LookupValueInTimeSeriesBatchOp,
    LookupVectorInTimeSeriesBatchOp,
    ProphetPredictBatchOp,
    ProphetTrainBatchOp,
)
from .nlp2 import (
    NaiveBayesTextPredictBatchOp,
    NaiveBayesTextTrainBatchOp,
    StringApproxNearestNeighborPredictBatchOp,
    StringApproxNearestNeighborTrainBatchOp,
    TextApproxNearestNeighborPredictBatchOp,
    TextApproxNearestNeighborTrainBatchOp,
    VectorApproxNearestNeighborPredictBatchOp,
    VectorApproxNearestNeighborTrainBatchOp,
)
from .graph2 import (
    CommunityDetectionClassifyBatchOp,
    HugeDeepWalkTrainBatchOp,
    HugeIndexerStringPredictBatchOp,
    HugeLabeledWord2VecTrainBatchOp,
    HugeLookupBatchOp,
    HugeMetaPath2VecTrainBatchOp,
    HugeMultiIndexerStringPredictBatchOp,
    HugeNode2VecTrainBatchOp,
    HugeWord2VecTrainBatchOp,
    IndexToNodeBatchOp,
    MdsBatchOp,
    Node2VecBatchOp,
    NodeIndexerTrainBatchOp,
    NodeToIndexBatchOp,
    RiskAlikeBuildGraphBatchOp,
    SimrankBatchOp,
)
from .feature4 import (
    ApplyAssociationRuleBatchOp,
    ApplySequenceRuleBatchOp,
    AutoCrossAlgoTrainBatchOp,
    AutoCrossTrainBatchOp,
    BaseCrossTrainBatchOp,
    BinarySelectorPredictBatchOp,
    BinarySelectorTrainBatchOp,
    BinningTrainForScorecardBatchOp,
    ConstrainedBinarySelectorPredictBatchOp,
    ConstrainedBinarySelectorTrainBatchOp,
    ConstrainedDivergenceTrainBatchOp,
    ConstrainedLinearRegTrainBatchOp,
    ConstrainedLogisticRegressionTrainBatchOp,
    ConstrainedRegSelectorPredictBatchOp,
    ConstrainedRegSelectorTrainBatchOp,
    CrossCandidateSelectorPredictBatchOp,
    CrossCandidateSelectorTrainBatchOp,
    CrossFeaturePredictBatchOp,
    CrossFeatureTrainBatchOp,
    GlmEvaluationBatchOp,
    GroupedFpGrowthBatchOp,
    HashCrossFeatureBatchOp,
    MultiCollinearityBatchOp,
    RegressionSelectorPredictBatchOp,
    RegressionSelectorTrainBatchOp,
    WoePredictBatchOp,
    WoeTrainBatchOp,
)
from .clustering2 import (
    GroupEmBatchOp,
    GroupGeoDbscanBatchOp,
    GroupGeoDbscanModelBatchOp,
)
from .script import JaxScriptBatchOp
from .io2 import (
    AggLookupBatchOp,
    BertTextEmbeddingBatchOp,
    BertTextPairClassifierPredictBatchOp,
    BertTextPairRegressorPredictBatchOp,
    BertTextPairRegressorTrainBatchOp,
    CatalogSinkBatchOp,
    CatalogSourceBatchOp,
    HBaseSinkBatchOp,
    InternalFullStatsBatchOp,
    LinearRegStepwisePredictBatchOp,
    LinearRegStepwiseTrainBatchOp,
    LookupHBaseBatchOp,
    LookupRedisRowBatchOp,
    LookupRedisStringBatchOp,
    RedisRowSinkBatchOp,
    RedisStringSinkBatchOp,
    TF2TableModelTrainBatchOp,
    TFRecordDatasetSinkBatchOp,
    TFRecordDatasetSourceBatchOp,
    TFTableModelClassifierPredictBatchOp,
    TFTableModelClassifierTrainBatchOp,
    TFTableModelPredictBatchOp,
    TFTableModelRegressorPredictBatchOp,
    TFTableModelRegressorTrainBatchOp,
    TFTableModelTrainBatchOp,
    TensorFlow2BatchOp,
    TensorFlowBatchOp,
    WriteTensorToImageBatchOp,
    XGBoostRegPredictBatchOp,
    XGBoostRegTrainBatchOp,
    XlsSinkBatchOp,
)
from .misc2 import (
    AddressParserBatchOp,
    PSIBatchOp,
    SomBatchOp,
    SparseFeatureIndexerPredictBatchOp,
    SparseFeatureIndexerTrainBatchOp,
)
from .misc2 import (
    BaseFormatTransBatchOp,
    BaseNearestNeighborTrainBatchOp,
    BaseRecommBatchOp,
    BaseSinkBatchOp,
    BaseSourceBatchOp,
    BaseSqlApiBatchOp,
)
