from .healthlnk import (  # noqa: F401
    check_rows,
    generate_healthlnk,
    plaintext_oracle,
    ICD9_CIRCULATORY,
    ICD9_HEART_414,
    MED_ASPIRIN,
    DOSAGE_325MG,
    DIAG_HEART_DISEASE,
)
from .queries import (  # noqa: F401
    comorbidity_plan,
    dosage_study_plan,
    aspirin_count_plan,
    three_join_plan,
    all_query_plans,
)
